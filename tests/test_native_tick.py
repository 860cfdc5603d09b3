"""Native per-tick fast path (hostkernel.cpp rk_tick) gates.

The Python paths in engine/engine.py stay the semantics owner; this suite
pins the native fast path to them:

- fixed-schedule conformance through the shared gate
  (testing.conformance.run_schedule_on_both_tick_paths): identical
  decision ledgers + byte-identical replica state, native vs
  ``RABIA_PY_TICK=1``;
- a MIXED cluster (native + Python replicas interleaved) — every frame
  the C emitter writes must be consumed by the Python ingest and vice
  versa, on the same wire;
- C-emitted frames decode through the Python BinarySerializer (wire
  conformance of the native outbound framing);
- ingest edge cases: spoofed envelopes dropped, future votes carried,
  stale votes reported to the repair path;
- the config-1 serial-latency budget regression test: proposer-direct
  commit p50 under budget with the fast path on.

The randomized twin of the conformance gate lives in
``scripts/fuzz_conformance.py --tick`` (fresh schedules every run).
"""

from __future__ import annotations

import asyncio
import os
import time

import numpy as np
import pytest

from rabia_tpu.native.build import load_hostkernel

_lib = load_hostkernel()
pytestmark = pytest.mark.skipif(
    _lib is None or not hasattr(_lib, "rk_ctx_create"),
    reason="native hostkernel unavailable",
)


def _mk_cluster(n_shards=1, n_replicas=3, py_rows=(), sm_factory=None, **cfg_kw):
    """In-memory cluster; replicas whose row is in `py_rows` are forced
    onto the Python tick path (mixed-cluster wire conformance).
    ``sm_factory`` overrides the per-replica state machine (default
    InMemoryStateMachine)."""
    from rabia_tpu.core.config import RabiaConfig
    from rabia_tpu.core.network import ClusterConfig
    from rabia_tpu.core.state_machine import InMemoryStateMachine
    from rabia_tpu.core.types import NodeId
    from rabia_tpu.engine import RabiaEngine
    from rabia_tpu.net import InMemoryHub

    kw = dict(
        phase_timeout=2.0, heartbeat_interval=0.05, round_interval=0.001
    )
    kw.update(cfg_kw)
    cfg = RabiaConfig(**kw).with_kernel(
        num_shards=n_shards, shard_pad_multiple=max(1, n_shards)
    )
    hub = InMemoryHub()
    nodes = [NodeId.from_int(i + 1) for i in range(n_replicas)]
    engines, sms = [], []
    prev = os.environ.pop("RABIA_PY_TICK", None)
    try:
        for i, node in enumerate(nodes):
            if i in py_rows:
                os.environ["RABIA_PY_TICK"] = "1"
            else:
                os.environ.pop("RABIA_PY_TICK", None)
            sm = (
                sm_factory() if sm_factory is not None
                else InMemoryStateMachine()
            )
            sms.append(sm)
            engines.append(
                RabiaEngine(
                    ClusterConfig.new(node, nodes), sm,
                    hub.register(node), config=cfg,
                )
            )
    finally:
        if prev is None:
            os.environ.pop("RABIA_PY_TICK", None)
        else:
            os.environ["RABIA_PY_TICK"] = prev
    return hub, nodes, engines, sms


async def _start(engines):
    tasks = [asyncio.ensure_future(e.run()) for e in engines]
    for _ in range(300):
        await asyncio.sleep(0.01)
        sts = [await e.get_statistics() for e in engines]
        if all(s.has_quorum for s in sts):
            return tasks
    raise AssertionError("cluster never formed quorum")


async def _stop(engines, tasks):
    for e in engines:
        await asyncio.wait_for(e.shutdown(), 10.0)
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)


class TestTickPathConformance:
    @pytest.mark.asyncio
    async def test_fixed_schedules_identical(self):
        from rabia_tpu.testing.conformance import (
            run_schedule_on_both_tick_paths,
        )

        schedule = [
            {0: ["SET a 1", "SET b 2"], 1: ["SET c 3"]},
            {1: ["SET c 4"]},
            {0: ["SET a 7"], 1: ["SET d 5", "SET e 6"]},
        ]
        await run_schedule_on_both_tick_paths(
            schedule, n_shards=2, n_replicas=3, tag="fixed-2s3r"
        )

    @pytest.mark.asyncio
    async def test_fixed_schedule_five_replicas(self):
        from rabia_tpu.testing.conformance import (
            run_schedule_on_both_tick_paths,
        )

        schedule = [{0: ["SET x 1"]}, {0: ["SET x 2"]}, {0: ["SET y 9"]}]
        await run_schedule_on_both_tick_paths(
            schedule, n_shards=1, n_replicas=5, tag="fixed-1s5r"
        )

    @pytest.mark.asyncio
    async def test_mixed_cluster_converges(self):
        """Native and Python replicas on the SAME wire: C-emitted frames
        feed the Python ingest and Python-emitted frames feed the C
        ingest; commits and state must still converge."""
        from rabia_tpu.core.types import Command, CommandBatch

        hub, nodes, engines, sms = _mk_cluster(py_rows=(1,))
        assert engines[0]._rk is not None
        assert engines[1]._rk is None  # forced Python path
        assert engines[2]._rk is not None
        tasks = await _start(engines)
        try:
            for i in range(12):
                fut = await engines[i % 3].submit_batch(
                    CommandBatch.new([Command.new(f"SET k{i} v{i}".encode())])
                )
                got = await asyncio.wait_for(fut, 15.0)
                assert got == [b"OK"]
            snap = sms[0].create_snapshot().data
            for _ in range(500):
                if all(s.create_snapshot().data == snap for s in sms):
                    break
                await asyncio.sleep(0.01)
            assert all(s.create_snapshot().data == snap for s in sms)
        finally:
            await _stop(engines, tasks)


class TestNativeWire:
    @pytest.mark.asyncio
    async def test_emitted_frames_decode_via_python_codec(self):
        """Every frame the native tick writes must decode through the
        Python BinarySerializer (wire-format ownership stays with the
        Python codec)."""
        from rabia_tpu.core.messages import (
            Decision,
            VoteRound1,
            VoteRound2,
        )
        from rabia_tpu.core.serialization import BinarySerializer
        from rabia_tpu.core.types import Command, CommandBatch, NodeId

        hub, nodes, engines, sms = _mk_cluster()
        observer = NodeId.from_int(99)
        obs_net = hub.register(observer)
        tasks = await _start(engines)
        try:
            for i in range(4):
                fut = await engines[0].submit_batch(
                    CommandBatch.new([Command.new(b"SET k v")])
                )
                await asyncio.wait_for(fut, 15.0)
        finally:
            await _stop(engines, tasks)
        ser = BinarySerializer()
        kinds = set()
        n_frames = 0
        while True:
            item = obs_net.receive_nowait()
            if item is None:
                break
            sender, data = item
            msg = ser.deserialize(data)  # raises on any malformed frame
            assert msg.sender == sender
            kinds.add(type(msg.payload).__name__)
            if isinstance(msg.payload, (VoteRound1, VoteRound2)):
                assert len(msg.payload) >= 1
                assert int(msg.payload.vals.max()) <= 3
            if isinstance(msg.payload, Decision):
                assert msg.payload.bids is None
            n_frames += 1
        assert n_frames > 0
        # the consensus wave kinds, all native-framed
        assert {"VoteRound1", "VoteRound2", "Decision"} <= kinds

    @pytest.mark.asyncio
    async def test_spoofed_envelope_dropped(self):
        """A frame whose envelope sender differs from the transport-
        authenticated peer must be dropped by the native ingest (same
        guard as engine._handle_message)."""
        from rabia_tpu.core.messages import ProtocolMessage, VoteRound1
        from rabia_tpu.core.serialization import BinarySerializer

        hub, nodes, engines, sms = _mk_cluster()
        e0 = engines[0]
        rk = e0._rk
        assert rk is not None
        ser = BinarySerializer()
        # envelope claims node 2 (row 2); we present it as from row 1
        spoofed = ser.serialize(
            ProtocolMessage.new(
                nodes[2],
                VoteRound1(
                    shards=np.asarray([0]),
                    phases=np.asarray([0]),
                    vals=np.asarray([1], np.int8),
                ),
            )
        )
        before = rk.dropped_frames
        assert rk.ingest(spoofed, 1, time.time()) == -1
        assert rk.dropped_frames == before + 1

    @pytest.mark.asyncio
    async def test_future_votes_carried_and_stale_reported(self):
        from rabia_tpu.core.messages import ProtocolMessage, VoteRound1
        from rabia_tpu.core.serialization import BinarySerializer

        hub, nodes, engines, sms = _mk_cluster()
        e0 = engines[0]
        rk = e0._rk
        ser = BinarySerializer()
        # a vote for a far-future slot: carried, not scattered
        fut_vote = ser.serialize(
            ProtocolMessage.new(
                nodes[1],
                VoteRound1(
                    shards=np.asarray([0]),
                    phases=np.asarray([5 << 16]),
                    vals=np.asarray([1], np.int8),
                ),
            )
        )
        assert rk.ingest(fut_vote, 1, time.time()) == 1
        assert rk.carry_count == 1
        assert int(e0.rt.votes_seen_slot[0]) == 5
        # a stale vote (slot below applied): reported for repair, rc=2
        e0.rt.applied_upto[0] = 3
        stale_vote = ser.serialize(
            ProtocolMessage.new(
                nodes[1],
                VoteRound1(
                    shards=np.asarray([0]),
                    phases=np.asarray([1 << 16]),
                    vals=np.asarray([0], np.int8),
                ),
            )
        )
        assert rk.ingest(stale_vote, 1, time.time()) == 2


class TestSerialLatencyBudget:
    @pytest.mark.asyncio
    @pytest.mark.parametrize(
        "mode", ["plain", "traced", "flight", "apply"]
    )
    async def test_config1_serial_latency_budget(self, mode):
        """Pin the config-1 regression (p50 1.6 → 2.49 ms, CPU host):
        proposer-direct serial commits through the native tick
        path must hold a p50 budget. The budget is sized for a loaded
        2-core CI host — the Python tick path measures ~4.2-4.7 ms here,
        the native path ~2.3 ms, so the gate catches a regression to the
        Python-path cost class while tolerating host noise. Best-of-two
        rounds to shrug off one noisy measurement window.

        The ``traced`` variant is the observability overhead guard: the
        SAME budget must hold with span tracing enabled (RABIA_TRACE=1
        semantics) and the metrics registry live — instrumentation on
        the hot path is bounded to span bookkeeping plus event-path
        histogram observes, and the disabled path stays one branch.

        The ``flight`` variant is the recorder-on overhead guard: the
        native flight ring is always written on the C fast path (a
        clock_gettime + one 32-byte store per record), and the same
        budget must hold with it verifiably populated — the variant
        additionally asserts the ring carried the run's lifecycle, so a
        silently-disabled recorder can't make the guard vacuous.

        The ``apply`` variant runs the same budget through the NATIVE
        APPLY PLANE (kvstore shard stores on the statekernel, binary
        SET commands): serial commits must not regress when the apply
        side of the commit path is the C plane, and the variant asserts
        the plane actually applied (SKC op counter + its flight ring),
        so a silent fallback to the Python store can't make it vacuous."""
        trace = mode == "traced"
        from rabia_tpu.core.tracing import tracer
        from rabia_tpu.core.types import Command, CommandBatch
        from rabia_tpu.engine.leader import slot_proposer

        # sized against this PR's recorded spread on a 2-core host
        # (engine_sweep_r06: native p50 median 2.15 ms with slow repeats
        # near 3.6 ms under scheduler noise; the Python path measures
        # 4.2-4.7 ms) — best-of-3 rounds under 4.5 ms separates the two
        # cost classes without going red on one noisy window. The budget
        # is additionally LOAD-AWARE (the documented ~1-in-4 ambient-load
        # flake class): a saturating co-tenant scales it, capped at 2x —
        # a regression to the Python-path cost class still trips it.
        budget_ms = 4.5
        try:
            load = os.getloadavg()[0] / max(1, os.cpu_count() or 1)
        except OSError:  # pragma: no cover - platform without loadavg
            load = 0.0
        budget_ms *= max(1.0, min(2.0, load))
        sm_factory = None
        if mode == "apply":
            from rabia_tpu.apps.native_store import native_apply_available
            from rabia_tpu.apps.sharded import make_sharded_kv

            if not native_apply_available():
                pytest.skip("statekernel library unavailable")
            sm_factory = lambda: make_sharded_kv(1, native=True)[0]  # noqa: E731
        hub, nodes, engines, sms = _mk_cluster(
            phase_timeout=0.4, sm_factory=sm_factory,
        )
        assert all(e._rk is not None for e in engines)
        prev_enabled = tracer.enabled
        if trace:
            tracer.enabled = True
        if mode == "apply":
            from rabia_tpu.apps.kvstore import encode_set_bin

            cmd_bytes = encode_set_bin("k", "v")  # the binary wire op
        else:
            cmd_bytes = b"SET k v"
        tasks = await _start(engines)
        try:
            best = float("inf")
            for _round in range(3):
                lat = []
                for i in range(60):
                    e = engines[0]
                    slot = max(
                        int(e.rt.next_slot[0]), int(e.rt.applied_upto[0])
                    )
                    p = slot_proposer(0, slot, 3)
                    t0 = time.perf_counter()
                    fut = await engines[p].submit_batch(
                        CommandBatch.new([Command.new(cmd_bytes)])
                    )
                    await asyncio.wait_for(fut, 10.0)
                    lat.append(time.perf_counter() - t0)
                lat.sort()
                best = min(best, lat[len(lat) // 2] * 1000)
                if best <= budget_ms:
                    break
            # on failure, carry the runtime stage profiler's breakdown
            # (rabia_runtime_stage_seconds): the documented ambient-load
            # flake class becomes a diagnosable report — a co-tenant
            # starving the loop shows up as idle/other dominating, a
            # real regression shows up in ingest/tick/apply
            stages = engines[0].stage_seconds()
            total_s = sum(stages.values()) or 1.0
            breakdown = ", ".join(
                f"{k}={v:.3f}s ({v / total_s * 100:.0f}%)"
                for k, v in sorted(
                    stages.items(), key=lambda kv: -kv[1]
                )
                if v > 0
            )
            assert best <= budget_ms, (
                f"serial commit p50 {best:.2f} ms exceeds the "
                f"{budget_ms} ms budget (config-1 latency regression"
                f"{', tracing ON' if trace else ''}); "
                f"stage breakdown: {breakdown}"
            )
            if trace:
                # the spans must actually have been aggregated (the guard
                # is vacuous if tracing silently stayed off) and fold
                # into the replica metrics exposition
                assert tracer.spans, "tracing enabled but no spans recorded"
                assert "rabia_span_seconds" in (
                    engines[0].metrics.render_prometheus()
                )
            if mode == "flight":
                # the native ring must have recorded the run it just
                # timed (otherwise this variant guards nothing)
                e0 = engines[0]
                assert e0._rk.flight_head() > 0
                kinds = {e["kind"] for e in e0.flight_events()}
                assert {"frame_in", "open", "decide", "apply"} <= kinds
            if mode == "apply":
                # the native apply plane must actually have applied the
                # run (otherwise this variant guards nothing)
                e0 = engines[0]
                plane = e0.sm._native_plane
                assert plane is not None
                assert plane.counter("ops") >= 60
                assert plane.flight_head() > 0
            # the commit pipeline histograms observed every commit
            h = engines[0].metrics.histogram(
                "commit_stage_seconds", labels={"stage": "propose_decide"}
            )
            assert h.count > 0
        finally:
            if trace:
                tracer.enabled = prev_enabled
                tracer.reset()
            await _stop(engines, tasks)
