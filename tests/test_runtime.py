"""Native engine runtime (native/runtime.cpp + engine/runtime_bridge.py).

Covers: activation preconditions, scalar + block commits through the
GIL-free io/tick thread, the zero-GIL-per-wave acceptance counter (and
its /metrics exposure), runtime-vs-asyncio conformance on fixed
schedules, shutdown ordering (runtime drain -> apply flush -> transport
close) including a mid-wave shutdown that must not lose staged result
frames, and the runtime flight-recorder kinds.

The asyncio orchestration stays the semantics owner: RABIA_PY_RUNTIME=1
forces it; scripts/fuzz_conformance.py --runtime draws fresh schedules.
"""

from __future__ import annotations

import asyncio
import time

import numpy as np
import pytest

from rabia_tpu.apps import make_sharded_kv
from rabia_tpu.apps.kvstore import encode_set_bin
from rabia_tpu.core.blocks import build_block
from rabia_tpu.core.config import RabiaConfig, TcpNetworkConfig
from rabia_tpu.core.network import ClusterConfig
from rabia_tpu.core.types import Command, CommandBatch, NodeId
from rabia_tpu.engine import RabiaEngine
from rabia_tpu.engine.leader import slot_proposer_vec
from rabia_tpu.net.tcp import TcpNetwork


def _runtime_lib():
    from rabia_tpu.native.build import load_runtime

    return load_runtime()


pytestmark = pytest.mark.skipif(
    _runtime_lib() is None, reason="native runtime library unavailable"
)


async def _mk_cluster(S: int, R: int, **cfg_kw):
    ids = [NodeId.from_int(i + 1) for i in range(R)]
    nets = [TcpNetwork(i, TcpNetworkConfig(bind_port=0)) for i in ids]
    for i in range(R):
        for j in range(R):
            if i != j:
                nets[i].add_peer(ids[j], "127.0.0.1", nets[j].port)
    cfg = RabiaConfig(
        phase_timeout=cfg_kw.pop("phase_timeout", 2.0),
        heartbeat_interval=0.05,
        round_interval=0.002,
    ).with_kernel(num_shards=S, shard_pad_multiple=max(1, S))
    engines, machines, tasks = [], [], []
    for i, n in enumerate(ids):
        sm, ms = make_sharded_kv(S)
        machines.append(ms)
        e = RabiaEngine(ClusterConfig.new(n, ids), sm, nets[i], config=cfg)
        engines.append(e)
        tasks.append(asyncio.ensure_future(e.run()))
    for _ in range(600):
        await asyncio.sleep(0.01)
        if all([(await e.get_statistics()).has_quorum for e in engines]):
            break
    else:
        raise AssertionError("cluster never formed quorum")
    return ids, nets, engines, machines, tasks


async def _teardown(engines, tasks, nets):
    for e in engines:
        await e.shutdown()
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    for n in nets:
        await n.close()


def _own_shards(e, S: int) -> np.ndarray:
    shard_ids = np.arange(S)
    head = np.maximum(e.rt.next_slot[:S], e.rt.applied_upto[:S])
    return shard_ids[
        (slot_proposer_vec(shard_ids, head, e.R) == e.me)
        & (e.rt.queue_len[:S] == 0)
        & ~e.rt.in_flight[:S]
    ]


class TestRuntimeActivation:
    def test_active_on_tcp_inactive_on_env(self, monkeypatch):
        async def run():
            _, nets, engines, _, tasks = await _mk_cluster(4, 3)
            try:
                assert all(e._rtm is not None for e in engines)
                assert all(e.health()["native_runtime"] for e in engines)
                # the transport's Python reader is detached: the runtime
                # thread owns the inbox
                assert all(n._reader_detached for n in nets)
            finally:
                await _teardown(engines, tasks, nets)

        asyncio.run(run())

        async def run_forced():
            _, nets, engines, _, tasks = await _mk_cluster(4, 3)
            try:
                assert all(e._rtm is None for e in engines)
            finally:
                await _teardown(engines, tasks, nets)

        monkeypatch.setenv("RABIA_PY_RUNTIME", "1")
        asyncio.run(run_forced())

    def test_inactive_on_inmemory_hub(self):
        async def run():
            from rabia_tpu.net import InMemoryHub

            hub = InMemoryHub()
            ids = [NodeId.from_int(i + 1) for i in range(3)]
            engines = [
                RabiaEngine(
                    ClusterConfig.new(n, ids),
                    make_sharded_kv(2)[0],
                    hub.register(n),
                    config=RabiaConfig().with_kernel(
                        num_shards=2, shard_pad_multiple=2
                    ),
                )
                for n in ids
            ]
            assert all(e._rtm is None for e in engines)

        asyncio.run(run())


class TestRuntimeCommit:
    def test_scalar_and_block_commit_and_gil_counter(self):
        async def run():
            S, R = 8, 3
            _, nets, engines, machines, tasks = await _mk_cluster(S, R)
            try:
                e0 = engines[0]
                # scalar commit
                fut = await e0.submit_batch(
                    CommandBatch.new(
                        [Command.new(encode_set_bin("k", "v"))], shard=1
                    ),
                    shard=1,
                )
                res = await asyncio.wait_for(fut, 10.0)
                assert len(res) == 1 and res[0][0] == 0  # ok result frame
                gil_before = e0._rtm.counter("gil_handoffs")
                waves_before = e0._rtm.counter("waves_native")
                # block-only waves on each engine's own shards: the
                # decide->apply->result path must never take the GIL
                for _ in range(5):
                    futs = []
                    for e in engines:
                        mine = _own_shards(e, S)
                        if len(mine) == 0:
                            continue
                        futs.append(
                            await e.submit_block(
                                build_block(
                                    mine,
                                    [
                                        [encode_set_bin(f"k{int(s)}", "v")]
                                        for s in mine
                                    ],
                                )
                            )
                        )
                    results = await asyncio.wait_for(
                        asyncio.gather(*futs), 20.0
                    )
                    for r in results:
                        for entry in r:
                            assert not isinstance(entry, Exception)
                assert e0._rtm.counter("waves_native") > waves_before
                assert e0._rtm.counter("gil_handoffs") == gil_before, (
                    "steady-state native waves took a GIL handoff"
                )
                # /metrics exposure of the acceptance counter
                snap = e0.metrics.snapshot()
                assert snap.get("rabia_engine_native_runtime") == 1
                assert snap.get("rabia_runtime_waves_native_total", 0) > 0
                assert "rabia_runtime_gil_handoffs_total" in snap
                # replica state converges
                await asyncio.sleep(0.3)
                want = [m.store.checksum() for m in machines[0]]
                for _ in range(200):
                    if all(
                        [m.store.checksum() for m in ms] == want
                        for ms in machines
                    ):
                        break
                    await asyncio.sleep(0.01)
                assert all(
                    [m.store.checksum() for m in ms] == want
                    for ms in machines
                )
            finally:
                await _teardown(engines, tasks, nets)

        asyncio.run(run())

    def test_flight_runtime_kinds_present(self):
        async def run():
            S, R = 4, 3
            _, nets, engines, _, tasks = await _mk_cluster(S, R)
            try:
                e0 = engines[0]
                fut = await e0.submit_batch(
                    CommandBatch.new(
                        [Command.new(encode_set_bin("fk", "fv"))], shard=0
                    ),
                    shard=0,
                )
                await asyncio.wait_for(fut, 10.0)
                kinds = {ev["kind"] for ev in e0.flight_events()}
                assert "rt_wake" in kinds, kinds
                assert "rt_handoff" in kinds, kinds
                # lifecycle records still present alongside
                assert {"submit", "propose", "decide", "apply"} <= kinds
            finally:
                await _teardown(engines, tasks, nets)

        asyncio.run(run())


class TestRuntimeConformance:
    def test_fixed_schedules_match_asyncio_owner(self):
        from rabia_tpu.testing.conformance import (
            run_schedule_on_runtime_paths,
        )

        schedule = [
            {0: [("a", "1")], 1: [("b", "2"), ("c", "3")]},
            {0: [("a", "4")], 2: [("d", "5")]},
            {1: [("b", "6")], 2: [("e", "7")], 0: [("f", "8")]},
            {0: [("a", "9")], 1: [("g", "10")]},
        ]
        asyncio.run(
            run_schedule_on_runtime_paths(
                schedule, n_shards=3, n_replicas=3, tag="fixed-runtime"
            )
        )


class TestRuntimeShutdown:
    def test_shutdown_ordering_clean(self):
        """Runtime drain -> apply flush -> transport close: state and
        counters survive shutdown; the transport closes last."""

        async def run():
            S, R = 4, 3
            _, nets, engines, machines, tasks = await _mk_cluster(S, R)
            e0 = engines[0]
            fut = await e0.submit_batch(
                CommandBatch.new(
                    [Command.new(encode_set_bin("sk", "sv"))], shard=0
                ),
                shard=0,
            )
            await asyncio.wait_for(fut, 10.0)
            await _teardown(engines, tasks, nets)
            # post-shutdown: frozen counters and flight stay readable
            assert e0._rtm.counter("frames_native") > 0
            assert len(e0.flight_events()) > 0
            assert machines[0][0].store.get("sk").value == "sv"

        asyncio.run(run())

    def test_mid_wave_shutdown_keeps_staged_results(self):
        """A decided wave whose result frames are staged in the event
        mailbox when shutdown starts must still settle the submitter's
        future: stop() finishes the runtime iteration and drains the
        mailbox BEFORE the transport closes."""

        async def run():
            S, R = 8, 3
            _, nets, engines, machines, tasks = await _mk_cluster(S, R)
            e0 = engines[0]
            mine = _own_shards(e0, S)
            assert len(mine) > 0
            fut = await e0.submit_block(
                build_block(
                    mine,
                    [[encode_set_bin(f"m{int(s)}", "w")] for s in mine],
                )
            )
            # push the wave command down WITHOUT letting the event loop
            # drain the mailbox, then block the loop synchronously while
            # the C threads decide and apply the wave — the staged
            # results sit in the event ring when shutdown begins
            e0._rtm.pump()
            deadline = time.time() + 5.0
            while (
                e0._rtm.counter("slots_applied") < len(mine)
                and time.time() < deadline
            ):
                time.sleep(0.01)  # deliberately sync: no drain can run
            assert e0._rtm.counter("slots_applied") >= len(mine), (
                "wave never applied natively"
            )
            assert not fut.done(), "future settled without a drain?"
            await e0.shutdown()  # runtime drain happens in here
            assert fut.done(), "mid-wave shutdown lost staged results"
            res = fut.result()
            assert len(res) == len(mine)
            for entry in res:
                assert not isinstance(entry, Exception)
                assert len(entry) == 1 and bytes(entry[0])[0] == 0
            for e in engines[1:]:
                await e.shutdown()
            for t in tasks:
                t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)
            for n in nets:
                await n.close()

        asyncio.run(run())


class TestShardGroupRuntime:
    """Thread-per-shard-group runtime (round 14): N C worker threads,
    each owning a contiguous shard group end-to-end. workers=1 stays the
    byte-for-byte historical runtime; these tests pin the multi-worker
    geometry, routing, per-worker observability and conformance."""

    def test_multi_worker_activation_and_commits(self, monkeypatch):
        monkeypatch.setenv("RABIA_RT_WORKERS", "2")

        async def run():
            S, R = 8, 3
            _, nets, engines, machines, tasks = await _mk_cluster(S, R)
            try:
                e0 = engines[0]
                rtm = e0._rtm
                assert rtm is not None and rtm.workers == 2
                assert rtm._chunk == 4  # contiguous groups [0,4) [4,8)
                assert rtm._group_of(0) == 0 and rtm._group_of(7) == 1
                # submit on shards of BOTH groups; every commit must land
                for s in (0, 2, 4, 7):
                    fut = await e0.submit_batch(
                        CommandBatch.new(
                            [Command.new(encode_set_bin(f"g{s}", "v"))],
                            shard=s,
                        ),
                        shard=s,
                    )
                    res = await asyncio.wait_for(fut, 10.0)
                    assert len(res) == 1 and res[0][0] == 0
                # both workers ran their loops and committed slots
                pw = [
                    rtm.counters_dict_worker(g) for g in range(rtm.workers)
                ]
                assert all(d["loops"] > 0 for d in pw)
                committed = [
                    d["decided_scalar"] + d["waves_native"] for d in pw
                ]
                assert all(cnt > 0 for cnt in committed), committed
                # aggregate counters = per-worker sums
                assert rtm.counter("decided_scalar") == sum(
                    d["decided_scalar"] for d in pw
                )
                # per-worker stage series carry the worker label on
                # /metrics next to the unlabeled aggregate
                text = e0.metrics.render_prometheus()
                assert 'rabia_runtime_stage_seconds{stage="tick"}' in text
                assert (
                    'worker="0"' in text and 'worker="1"' in text
                ), "per-worker stage series missing"
                # replica state converges across workers
                await asyncio.sleep(0.2)
                want = [m.store.checksum() for m in machines[0]]
                for _ in range(200):
                    if all(
                        [m.store.checksum() for m in ms] == want
                        for ms in machines
                    ):
                        break
                    await asyncio.sleep(0.01)
                assert all(
                    [m.store.checksum() for m in ms] == want
                    for ms in machines
                )
            finally:
                await _teardown(engines, tasks, nets)

        asyncio.run(run())

    def test_block_wave_across_groups_no_gil(self, monkeypatch):
        """A block wave spanning BOTH shard groups commits natively on
        every worker with zero GIL handoffs (the bridge splits it into
        group-pure CMD_OPEN_WAVE records; each worker applies through
        its own statekernel lane)."""
        monkeypatch.setenv("RABIA_RT_WORKERS", "2")

        async def run():
            S, R = 8, 3
            _, nets, engines, machines, tasks = await _mk_cluster(S, R)
            try:
                e0 = engines[0]
                rtm = e0._rtm
                gil_before = rtm.counter("gil_handoffs")
                waves_before = rtm.counter("waves_native")
                for _ in range(4):
                    futs = []
                    for e in engines:
                        mine = _own_shards(e, S)
                        if len(mine) == 0:
                            continue
                        futs.append(
                            await e.submit_block(
                                build_block(
                                    mine,
                                    [
                                        [encode_set_bin(f"x{int(s)}", "y")]
                                        for s in mine
                                    ],
                                )
                            )
                        )
                    results = await asyncio.wait_for(
                        asyncio.gather(*futs), 20.0
                    )
                    for r in results:
                        for entry in r:
                            assert not isinstance(entry, Exception)
                assert rtm.counter("waves_native") > waves_before
                assert rtm.counter("gil_handoffs") == gil_before, (
                    "multi-worker native waves took a GIL handoff"
                )
            finally:
                await _teardown(engines, tasks, nets)

        asyncio.run(run())

    def test_workers_conformance_vs_asyncio_and_single(self):
        """workers=2 and workers=1 each pin identical decision ledgers,
        byte-identical client responses and state checksums against the
        asyncio owner — transitively, workers=2 == workers=1.

        One bounded retry per leg (the round-7 packet_loss_30pct
        precedent): under ambient load a retransmit can race a decide
        into one extra dedup'd slot on EITHER leg, which the strict
        full-ledger compare flags; a real conformance bug is
        deterministic on the fixed schedule and fails both attempts."""
        from rabia_tpu.testing.conformance import (
            run_schedule_on_runtime_paths,
        )

        schedule = [
            {0: [("a", "1")], 3: [("b", "2"), ("c", "3")]},
            {1: [("d", "4")], 2: [("e", "5")]},
            {0: [("f", "6")], 1: [("g", "7")], 3: [("h", "8")]},
            {2: [("e", "9")], 0: [("a", "10")]},
        ]
        for w in (2, 1):
            for attempt in (0, 1):
                try:
                    asyncio.run(
                        run_schedule_on_runtime_paths(
                            schedule, n_shards=4, n_replicas=3,
                            tag=f"fixed-runtime-w{w}", workers=w,
                        )
                    )
                    break
                except AssertionError:
                    if attempt:
                        raise

    def test_partition_never_yields_empty_or_inverted_group(self):
        """chunk = ceil(n / W) can yield fewer than W groups (8 shards
        over 7 workers is chunk 2 -> 4 groups; a 5th would start at
        lo=10 > hi=8). The resolved count is clamped to the groups the
        split yields, for every geometry incl. workers > shards."""
        from types import SimpleNamespace

        from rabia_tpu.engine.runtime_bridge import resolve_runtime_workers

        for n in range(1, 70):
            for asked in range(1, 80):
                eng = SimpleNamespace(
                    n_shards=n,
                    config=SimpleNamespace(runtime_workers=asked),
                )
                w = resolve_runtime_workers(eng)
                chunk = (n + w - 1) // w
                ranges = [
                    (g * chunk, n if g == w - 1 else (g + 1) * chunk)
                    for g in range(w)
                ]
                assert 1 <= w <= min(asked, 64, n)
                assert all(lo < hi <= n for lo, hi in ranges), (n, asked)
                assert ranges[0][0] == 0 and ranges[-1][1] == n
                assert all(
                    a[1] == b[0] for a, b in zip(ranges, ranges[1:])
                )
        eng = SimpleNamespace(
            n_shards=8, config=SimpleNamespace(runtime_workers=7)
        )
        assert resolve_runtime_workers(eng) == 4

    @pytest.mark.parametrize("S,asked,want", [(8, 7, 4), (5, 9, 5)])
    def test_uneven_worker_split_commits(self, monkeypatch, S, asked, want):
        """The geometry an 8-core host's auto count (cores - 1 = 7)
        produces for 8 shards, and workers > shards: block waves over
        every group commit natively (the unclamped split gave a worker
        lo > hi and a negative-size memset in collect_opens)."""
        monkeypatch.setenv("RABIA_RT_WORKERS", str(asked))

        async def run():
            R = 3
            _, nets, engines, machines, tasks = await _mk_cluster(S, R)
            try:
                rtm = engines[0]._rtm
                assert rtm is not None and rtm.workers == want
                assert len(rtm._extra_rks) == want - 1
                assert {rtm._group_of(s) for s in range(S)} == set(
                    range(want)
                )
                waves_before = rtm.counter("waves_native")
                for _ in range(3):
                    futs = []
                    for e in engines:
                        mine = _own_shards(e, S)
                        if len(mine) == 0:
                            continue
                        futs.append(
                            await e.submit_block(
                                build_block(
                                    mine,
                                    [
                                        [encode_set_bin(f"u{int(s)}", "v")]
                                        for s in mine
                                    ],
                                )
                            )
                        )
                    results = await asyncio.wait_for(
                        asyncio.gather(*futs), 20.0
                    )
                    for r in results:
                        for entry in r:
                            assert not isinstance(entry, Exception)
                assert rtm.counter("waves_native") > waves_before
            finally:
                await _teardown(engines, tasks, nets)

        asyncio.run(run())

    def test_workers_clamp_and_single_worker_identity(self, monkeypatch):
        """workers never exceed the shard count, and workers=1 keeps the
        historical single-ring geometry (no sibling rk contexts)."""
        monkeypatch.setenv("RABIA_RT_WORKERS", "8")

        async def run():
            S, R = 2, 3
            _, nets, engines, _, tasks = await _mk_cluster(S, R)
            try:
                rtm = engines[0]._rtm
                assert rtm is not None
                assert rtm.workers == 2  # clamped to n_shards
            finally:
                await _teardown(engines, tasks, nets)

        asyncio.run(run())

        monkeypatch.setenv("RABIA_RT_WORKERS", "1")

        async def run_single():
            S, R = 4, 3
            _, nets, engines, _, tasks = await _mk_cluster(S, R)
            try:
                rtm = engines[0]._rtm
                assert rtm is not None and rtm.workers == 1
                assert rtm._extra_rks == []
                assert engines[0]._rk.siblings == []
            finally:
                await _teardown(engines, tasks, nets)

        asyncio.run(run_single())
