"""BASELINE config sweep: the 5 target configurations, engine-driven.

Every config now exercises the FULL RabiaEngine stack (consensus kernel +
message routing + slot lifecycle + state-machine apply + client futures) —
the round-1 sweep measured the bare device pipeline for configs 2-4 with
app names as labels; this sweep fixes that.

Configs (BASELINE.md):
  1. counter_smr,  3 replicas,     1 shard,  in-memory      (latency-bound)
  2. kvstore_smr,  3 replicas,    64 shards, in-memory      (block lane)
  3. kvstore_smr,  5 replicas,  4096 shards, adaptive batching
  4. banking_smr,  7 replicas,  1024 shards, minority crash (3/7) mid-run
  5. kvstore_smr,  5 replicas, 16384 shards, native TCP, Zipf key load

Baselines measured on this host:
  - ``oracle``: the scalar weak-MVC oracle (consensus math only, zero
    engine/transport/apply cost — the most generous possible CPU number);
  - ``cpu_engine``: the same RabiaEngine driven through the SCALAR lane
    (one Propose/VoteEntry message set per shard-slot — the reference's
    per-instance execution model) at 4096 shards x 5 replicas. This is the
    BASELINE.json north-star comparison ("vs CPU engine at 4096 concurrent
    kvstore shards x 5 replicas under the in-memory transport").

Each line reports vs_baseline = value / cpu_engine (the north-star ratio)
and vs_oracle = value / oracle for scale.

Engine configs pin JAX to the CPU (the engine paces rounds from the
host; the host kernel is numpy, so these configs never use the chip). Device-kernel lines
(mode=device_kernel) are emitted separately by bench.py / micro benches.

Run: python benchmarks/baseline_sweep.py            (all configs)
     python benchmarks/baseline_sweep.py 2 3        (subset)
"""

from __future__ import annotations

import asyncio
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np


_LAST_TICK_PATH: str | None = None  # actual path of the last-built cluster
_LAST_PLANES: dict | None = None  # runtime|tick|apply planes, ground truth


def _note_tick_path(engines) -> None:
    """Record what the cluster's engines ACTUALLY run (engine._rk /
    engine._rtm / sm._native_plane are the ground truth — a native build
    failure or a bridge construction error falls back to the Python
    paths silently, and perf numbers must be attributable without
    reading env vars out of CI logs)."""
    global _LAST_TICK_PATH, _LAST_PLANES
    _LAST_TICK_PATH = (
        "native" if all(e._rk is not None for e in engines) else "python"
    )
    _LAST_PLANES = {
        "runtime": (
            "native"
            if all(e._rtm is not None for e in engines)
            else "python"
        ),
        "tick": _LAST_TICK_PATH,
        "apply": (
            "native"
            if all(
                getattr(e.sm, "_native_plane", None) is not None
                for e in engines
            )
            else "python"
        ),
        # thread-per-shard-group runtime: worker (= shard group) count
        # actually running (1 on the asyncio path) — every sweep line
        # records the geometry it measured
        "runtime_workers": (
            max(
                getattr(e._rtm, "workers", 1)
                for e in engines
                if e._rtm is not None
            )
            if any(e._rtm is not None for e in engines)
            else 1
        ),
    }


def _tick_path() -> str:
    """Best-effort label when no cluster was probed: library
    availability + the env toggle (the same preconditions RabiaEngine
    checks before attempting NativeTick construction)."""
    if _LAST_TICK_PATH is not None:
        return _LAST_TICK_PATH
    import os

    if os.environ.get("RABIA_PY_TICK") == "1":
        return "python"
    try:
        from rabia_tpu.native.build import load_hostkernel

        lib = load_hostkernel()
        if lib is not None and hasattr(lib, "rk_ctx_create"):
            return "native"
    except Exception:
        pass
    return "python"


def _emit(config: str, value: float, unit: str, baselines: dict, extra: dict) -> dict:
    doc = {
        "metric": "decisions_per_sec" if unit == "decisions/s" else unit,
        "config": config,
        "value": round(value, 1),
        "unit": unit,
        "tick_path": _tick_path(),
        # active planes of the measured cluster (runtime|tick|apply:
        # native|python) — perf numbers stay attributable without
        # reading env vars out of CI logs
        "planes": _LAST_PLANES
        or {"runtime": "python", "tick": _tick_path(), "apply": "python"},
        **extra,
    }
    if _LAST_OBS is not None:
        # counter context captured at the last cluster teardown — the
        # metrics-registry snapshot riding along with the throughput
        doc["obs"] = _LAST_OBS
    if baselines.get("cpu_engine"):
        doc["vs_baseline"] = round(value / baselines["cpu_engine"], 2)
        doc["baseline"] = "cpu_scalar_engine_4096shards_5rep"
        doc["baseline_cpu_engine_per_sec"] = round(baselines["cpu_engine"], 1)
    if baselines.get("oracle"):
        doc["vs_oracle"] = round(value / baselines["oracle"], 2)
        doc["baseline_oracle_per_sec"] = round(baselines["oracle"], 1)
    print(json.dumps(doc))
    return doc


def _lat_stats(lat_s: list) -> dict:
    """{settle_p50_ms, settle_p99_ms, settle_samples} from wave-settle
    latencies (seconds). Every config reports these, not just #1."""
    if not lat_s:
        return {"settle_p50_ms": None, "settle_p99_ms": None, "settle_samples": 0}
    xs = sorted(lat_s)
    return {
        "settle_p50_ms": round(xs[len(xs) // 2] * 1000, 2),
        "settle_p99_ms": round(xs[min(len(xs) - 1, int(len(xs) * 0.99))] * 1000, 2),
        "settle_samples": len(xs),
    }


def cpu_oracle_baseline(replicas: int = 5, sample: int = 120) -> float:
    from rabia_tpu.core.oracle import WeakMVCOracle
    from rabia_tpu.core.types import V1

    t0 = time.perf_counter()
    for _ in range(sample):
        o = WeakMVCOracle(replicas, [V1] * replicas, coin=lambda p: V1)
        for _ in range(64):
            o.step()
            if o.decided_value is not None:
                break
    return sample / (time.perf_counter() - t0)


# ---------------------------------------------------------------------------
# Shared cluster harness
# ---------------------------------------------------------------------------


def _cfg(S, phase_timeout=2.0, round_interval=0.0002, backend="host",
         device_substeps=3, heartbeat_interval=0.5):
    from rabia_tpu.core.config import RabiaConfig

    return RabiaConfig(
        phase_timeout=phase_timeout,
        heartbeat_interval=heartbeat_interval,
        round_interval=round_interval,
    ).with_kernel(
        num_shards=S,
        shard_pad_multiple=max(1, S),
        backend=backend,
        device_substeps=device_substeps,
    )


async def _mk_mem_cluster(S, R, sm_factory, **cfg_kw):
    from rabia_tpu.core.network import ClusterConfig
    from rabia_tpu.core.types import NodeId
    from rabia_tpu.engine import RabiaEngine
    from rabia_tpu.net import InMemoryHub

    nodes = [NodeId.from_int(i + 1) for i in range(R)]
    hub = InMemoryHub()
    engines, sms = [], []
    for n in nodes:
        sm = sm_factory()
        sms.append(sm)
        engines.append(
            RabiaEngine(ClusterConfig.new(n, nodes), sm, hub.register(n), config=_cfg(S, **cfg_kw))
        )
    _note_tick_path(engines)
    tasks = [asyncio.ensure_future(e.run()) for e in engines]
    for _ in range(500):
        await asyncio.sleep(0.01)
        sts = [await e.get_statistics() for e in engines]
        if all(s.has_quorum for s in sts):
            break
    return nodes, hub, engines, sms, tasks


_LAST_OBS: dict | None = None  # metrics snapshot of the last-stopped cluster


def _obs_snapshot(engines, nets=None) -> dict:
    """Counter context for one sweep config: decisions, drops, out-pool
    hit rate — pulled from replica 0's metrics registry and the native
    transport counter block, so BENCH rounds carry the WHY next to the
    throughput number (docs/OBSERVABILITY.md)."""
    e0 = engines[0]
    obs: dict = {}
    try:
        snap = e0.metrics.snapshot()
        obs = {
            "decided_v1": int(snap.get('rabia_engine_decided_total{value="v1"}', 0)),
            "decided_v0": int(snap.get('rabia_engine_decided_total{value="v0"}', 0)),
            "stale_votes": int(snap.get("rabia_tick_stale_votes_total", 0)),
            "slow_ticks": int(snap.get("rabia_engine_slow_ticks_total", 0)),
            "syncs": int(snap.get("rabia_engine_syncs_total", 0)),
            "ticks": int(snap.get("rabia_engine_ticks_total", 0)),
            "tick_frames": int(
                sum(
                    snap.get(f'rabia_tick_frames_total{{kind="{k}"}}', 0)
                    for k in ("vote1", "vote2", "decision")
                )
            ),
            "anomalies": e0.journal.counts(),
        }
    except Exception as e:  # the bench must never die on its own metrics
        obs["error"] = repr(e)
    if nets:
        try:
            hits, misses = nets[0].out_pool_stats
            total = hits + misses
            obs["out_pool_hits"] = int(hits)
            obs["out_pool_misses"] = int(misses)
            obs["out_pool_hit_rate"] = (
                round(hits / total, 4) if total else None
            )
            obs["inbox_dropped"] = int(
                nets[0].transport_counters().get("inbox_dropped", 0)
            )
        except Exception as e:
            obs["transport_error"] = repr(e)
    return obs


async def _stop(engines, tasks, nets=None):
    global _LAST_OBS
    # capture BEFORE teardown: the transport counter block dies with the
    # native handle
    _LAST_OBS = _obs_snapshot(engines, nets)
    for e in engines:
        try:
            await asyncio.wait_for(e.shutdown(), 5.0)
        except asyncio.TimeoutError:
            pass
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    for n in nets or []:
        await n.close()


async def _committed(engines):
    sts = [await e.get_statistics() for e in engines]
    return sum(s.committed_slots for s in sts) / len(engines), sts


async def _block_pump(engines, S, R, dur, shard_cmds, live=None, lat=None):
    """Drive the block lane: per cycle, each live engine proposes blocks
    for the shards it owns at their head slots. ``shard_cmds(s) -> list of
    command bytes`` for one slot of shard s. Returns commands acked.
    When ``lat`` (a list) is given, per-wave submit→settle latencies in
    seconds are appended to it."""
    from rabia_tpu.core.blocks import build_block
    from rabia_tpu.engine.leader import slot_proposer_vec

    live = live if live is not None else engines
    shard_ids = np.arange(S)
    stop_at = time.perf_counter() + dur
    acked = 0

    async def pump():
        nonlocal acked
        while time.perf_counter() < stop_at:
            futs = []
            sizes = []
            t_sub = time.perf_counter()
            for e in live:
                head = np.maximum(e.rt.next_slot[:S], e.rt.applied_upto[:S])
                mine = shard_ids[
                    (slot_proposer_vec(shard_ids, head, R) == e.me)
                    & (e.rt.queue_len[:S] == 0)
                    & ~e.rt.in_flight[:S]
                ]
                if len(mine) == 0:
                    continue
                cmds = [shard_cmds(int(s)) for s in mine]
                futs.append(await e.submit_block(build_block(mine, cmds)))
                sizes.append(sum(len(c) for c in cmds))
            if not futs:
                await asyncio.sleep(0.001)
                continue
            try:
                results = await asyncio.wait_for(
                    asyncio.gather(*futs), max(10.0, dur)
                )
                if lat is not None:
                    lat.append(time.perf_counter() - t_sub)
                for res in results:
                    counts = getattr(res, "group_counts", None)
                    if counts is not None:
                        # count acks without materializing responses
                        acked += int(counts().sum())
                    else:
                        acked += sum(
                            len(r)
                            for r in res
                            if not isinstance(r, Exception)
                        )
            except (asyncio.TimeoutError, Exception):
                await asyncio.sleep(0.02)

    await pump()
    return acked


# ---------------------------------------------------------------------------
# CPU-engine baseline (scalar lane — the reference's execution model)
# ---------------------------------------------------------------------------


async def _cpu_engine_rate(S=4096, R=5, dur=12.0) -> float:
    """The same engine, driven per shard-slot through the scalar lane:
    one Propose + per-entry votes per decision — the reference
    architecture's one-instance-at-a-time shape at full width. Fed gently
    (bounded submissions per pass) so the measurement reflects steady
    scalar-lane throughput rather than initial-burst queue collapse."""
    from rabia_tpu.apps import make_sharded_kv
    from rabia_tpu.apps.kvstore import encode_set_bin
    from rabia_tpu.core.types import Command, CommandBatch
    from rabia_tpu.engine.leader import slot_proposer_vec

    _, hub, engines, _, tasks = await _mk_mem_cluster(
        S, R, lambda: make_sharded_kv(S)[0]
    )
    shard_ids = np.arange(S)
    stop_at = time.perf_counter() + dur
    op = encode_set_bin("k", "v")

    async def feeder():
        while time.perf_counter() < stop_at:
            for e in engines:
                head = np.maximum(e.rt.next_slot[:S], e.rt.applied_upto[:S])
                mine = shard_ids[
                    (slot_proposer_vec(shard_ids, head, R) == e.me)
                    & (e.rt.queue_len[:S] < 1)
                ]
                for s in mine[:256]:
                    b = CommandBatch.new([Command.new(op)], shard=int(s))
                    try:
                        await e.submit_batch(b, shard=int(s))
                    except Exception:
                        pass
                await asyncio.sleep(0)
            await asyncio.sleep(0.002)

    # warmup third, measure the rest
    feed = asyncio.ensure_future(feeder())
    await asyncio.sleep(dur / 3)
    base, _ = await _committed(engines)
    t0 = time.perf_counter()
    await asyncio.sleep(2 * dur / 3)
    top, _ = await _committed(engines)
    dt = time.perf_counter() - t0
    feed.cancel()
    await asyncio.gather(feed, return_exceptions=True)
    await _stop(engines, tasks)
    return (top - base) / dt


# ---------------------------------------------------------------------------
# Configs
# ---------------------------------------------------------------------------


async def config1_counter(baselines) -> None:
    """Full engine stack: counter, 3 replicas, 1 shard, in-memory hub —
    sequential client: measures commit latency, not batch throughput."""
    from rabia_tpu.apps import CounterCommand, CounterSMR
    from rabia_tpu.core.smr import SMRBridge
    from rabia_tpu.core.types import Command, CommandBatch

    counters = []

    def factory():
        c = CounterSMR()
        counters.append(c)
        return SMRBridge(c)

    _, hub, engines, _, tasks = await _mk_mem_cluster(
        1, 3, factory, phase_timeout=0.4, round_interval=0.0005
    )
    codec = counters[0]
    n_ops = 100
    lat: list[float] = []
    t0 = time.perf_counter()
    for _ in range(n_ops):
        t_sub = time.perf_counter()
        fut = await engines[0].submit_batch(
            CommandBatch.new(
                [Command.new(codec.encode_command(CounterCommand.increment(1)))]
            )
        )
        await asyncio.wait_for(fut, 20.0)
        lat.append(time.perf_counter() - t_sub)
    dt = time.perf_counter() - t0
    assert counters[0].value == n_ops
    await _stop(engines, tasks)
    stats = _lat_stats(lat)
    return _emit(
        "1:counter_3rep_1shard_inmem",
        n_ops / dt,
        "decisions/s",
        baselines,
        {
            # real per-op percentiles now (was mean-as-p50)
            "p50_latency_ms": stats["settle_p50_ms"],
            "mode": "engine",
            "store": "counter_smr",
            **stats,
        },
    )


async def config2_kvstore_64(baselines) -> None:
    from rabia_tpu.apps import make_sharded_kv
    from rabia_tpu.apps.kvstore import encode_set_bin

    S, R = 64, 3
    _, hub, engines, _, tasks = await _mk_mem_cluster(
        S, R, lambda: make_sharded_kv(S)[0]
    )
    op = encode_set_bin("key", "value")
    lat: list[float] = []
    t0 = time.perf_counter()
    base, _ = await _committed(engines)
    await _block_pump(engines, S, R, 6.0, lambda s: [op], lat=lat)
    top, _ = await _committed(engines)
    dt = time.perf_counter() - t0
    await _stop(engines, tasks)
    return _emit(
        "2:kvstore_3rep_64shards_inmem",
        (top - base) / dt,
        "decisions/s",
        baselines,
        {
            "mode": "engine",
            "store": "kvstore_smr",
            "lane": "block",
            **_lat_stats(lat),
        },
    )


async def config3_kvstore_4096_batched(baselines) -> None:
    """kvstore, 5 replicas, 4096 shards. Two phases:
    (a) adaptive batching through the scalar lane (ShardedBatcher with
        size+time flush and +/-10% sizing) — commands amortize per slot;
    (b) the block lane at full width with 8 commands per slot — the bulk
        throughput number."""
    from rabia_tpu.apps import ShardedKVService, make_sharded_kv
    from rabia_tpu.apps.kvstore import encode_set_bin
    from rabia_tpu.core.config import BatchConfig

    S, R = 4096, 5
    sms = []

    def factory():
        sm, machines = make_sharded_kv(S)
        sms.append(machines)
        return sm

    _, hub, engines, _, tasks = await _mk_mem_cluster(S, R, factory)

    # (a) adaptive batcher on the scalar lane: 2000 ops over 64 hot shards
    svc = ShardedKVService(
        S,
        engines[0].submit_batch,
        sms[0],
        batching=BatchConfig(max_batch_size=100, max_batch_delay=0.01),
    )
    t0 = time.perf_counter()
    res = await asyncio.wait_for(
        asyncio.gather(
            *[svc.set(f"hot{i % 64}", f"v{i}") for i in range(2000)],
            return_exceptions=True,
        ),
        60.0,
    )
    adaptive_dt = time.perf_counter() - t0
    adaptive_ok = sum(
        1 for r in res if not isinstance(r, Exception) and getattr(r, "ok", False)
    )
    batches = sum(s.batches_created for s in svc.batch_stats)
    cmds = sum(s.commands_batched for s in svc.batch_stats)
    await svc.close()

    # (b) block lane, full width, one command per shard-slot (the
    # decisions/s headline), then a multi-command phase for commands/s
    one_op = [[encode_set_bin(f"k{s}", "v")] for s in range(S)]
    lat: list[float] = []
    t0 = time.perf_counter()
    base, _ = await _committed(engines)
    await _block_pump(engines, S, R, 8.0, lambda s: one_op[s], lat=lat)
    top, _ = await _committed(engines)
    dt = time.perf_counter() - t0
    rate = (top - base) / dt

    eight_ops = [
        [encode_set_bin(f"k{s}_{j}", "v") for j in range(8)] for s in range(S)
    ]
    t1 = time.perf_counter()
    base8, _ = await _committed(engines)
    await _block_pump(engines, S, R, 5.0, lambda s: eight_ops[s])
    top8, _ = await _committed(engines)
    dt8 = time.perf_counter() - t1
    await _stop(engines, tasks)
    # this config's OWN obs snapshot: the optional vector side-phase
    # below stops another cluster, which would overwrite the module
    # global and misattribute its counters to this config's doc
    kv_obs = _LAST_OBS

    # (c) same geometry on the columnar store (VectorShardedKV) — the
    # S-axis-native apply plane; the classic per-op store above is the
    # reference-parity path, this is the TPU-first one (config5's store).
    # Optional: a failure here must not discard the (a)/(b) measurements.
    vector_rate = None
    try:
        from rabia_tpu.apps.vector_kv import VectorShardedKV

        _, _, engines_v, _, tasks_v = await _mk_mem_cluster(
            S, R, lambda: VectorShardedKV(S, capacity=1 << 18)
        )
        tv = time.perf_counter()
        base_v, _ = await _committed(engines_v)
        await _block_pump(engines_v, S, R, 8.0, lambda s: one_op[s])
        top_v, _ = await _committed(engines_v)
        dt_v = time.perf_counter() - tv
        vector_rate = (top_v - base_v) / dt_v
        await _stop(engines_v, tasks_v)
    except Exception as e:
        print(f"config3 vector phase failed: {e!r}", file=sys.stderr)
    globals()["_LAST_OBS"] = kv_obs
    return _emit(
        "3:kvstore_5rep_4096shards_adaptive",
        rate,
        "decisions/s",
        baselines,
        {
            "mode": "engine",
            "store": "kvstore_smr",
            "lane": "block",
            "commands_per_slot": 1,
            **_lat_stats(lat),
            "batched_phase": {
                "commands_per_slot": 8,
                "decisions_per_sec": round((top8 - base8) / dt8, 1),
                "commands_per_sec": round((top8 - base8) * 8 / dt8, 1),
            },
            "adaptive_batching": {
                "ops": adaptive_ok,
                "consensus_batches": batches,
                "avg_batch_size": round(cmds / max(1, batches), 1),
                "ops_per_sec": round(adaptive_ok / adaptive_dt, 1),
            },
            "vector_store_phase": {
                "store": "vector_kv",
                "decisions_per_sec": (
                    round(vector_rate, 1) if vector_rate else None
                ),
            },
        },
    )


async def config4_banking_crash(baselines) -> None:
    """banking, 7 replicas, 1024 shards; 3 of 7 crash MID-RUN (engine-level
    fault: tasks cancelled + transport disconnected), survivors keep
    committing (f=3 tolerated)."""
    from rabia_tpu.apps import BankCommand, BankingSMR
    from rabia_tpu.apps.sharded import ShardedStateMachine

    S, R = 1024, 7
    all_machines = []

    def factory():
        machines = [BankingSMR() for _ in range(S)]
        all_machines.append(machines)
        return ShardedStateMachine(machines)

    nodes, hub, engines, _, tasks = await _mk_mem_cluster(
        S, R, factory, phase_timeout=0.4
    )
    codec = all_machines[0][0]
    dep = codec.encode_command(BankCommand.deposit("acct", 100))
    live = list(engines)

    # warm flow with all 7 up
    pre, _ = await _committed(engines[3:])
    t0 = time.perf_counter()
    await _block_pump(live, S, R, 3.0, lambda s: [dep])
    # CRASH replicas 0..2 (minority, f=3 tolerated with quorum 4)
    for i in range(3):
        tasks[i].cancel()
        hub.set_connected(nodes[i], False)
    live = engines[3:]
    crash_at, _ = await _committed(live)

    # post-crash load: live proposers ride the block lane; shards whose
    # rotation proposer is DEAD are submitted to a live replica through the
    # scalar lane, whose forward-timeout forces the null slot that rotates
    # the proposer (leaderless liveness under crash)
    from rabia_tpu.core.types import Command, CommandBatch
    from rabia_tpu.engine.leader import slot_proposer_vec

    shard_ids = np.arange(S)
    dead_rows = {0, 1, 2}
    post_dur = 8.0
    stop_at = time.perf_counter() + post_dur

    async def dead_shard_feeder():
        while time.perf_counter() < stop_at:
            e = live[0]
            head = np.maximum(e.rt.next_slot[:S], e.rt.applied_upto[:S])
            prop = slot_proposer_vec(shard_ids, head, R)
            stuck = shard_ids[
                np.isin(prop, list(dead_rows)) & (e.rt.queue_len[:S] < 1)
            ]
            for s in stuck[:512]:
                try:
                    await e.submit_batch(
                        CommandBatch.new([Command.new(dep)], shard=int(s)),
                        shard=int(s),
                    )
                except Exception:
                    pass
            await asyncio.sleep(0.05)

    feeder = asyncio.ensure_future(dead_shard_feeder())
    lat: list[float] = []
    await _block_pump(live, S, R, post_dur, lambda s: [dep], lat=lat)
    feeder.cancel()
    await asyncio.gather(feeder, return_exceptions=True)
    post, _ = await _committed(live)
    dt = time.perf_counter() - t0
    post_rate = (post - crash_at) / post_dur
    await _stop(engines[3:], tasks)
    return _emit(
        "4:banking_7rep_1024shards_minority_crash",
        post_rate,
        "decisions/s",
        baselines,
        {
            "mode": "engine",
            "store": "banking_smr",
            "lane": "block",
            "crashed_replicas": 3,
            "crash_kind": "engine task cancelled + transport disconnected mid-run",
            "survivor_committed_slots": int(post),
            **_lat_stats(lat),
        },
    )


async def config5_kvstore_tcp_zipf(baselines) -> None:
    """kvstore (vector store), 5 replicas, 16384 shards, native C++ TCP
    transport, Zipf-skewed keys: hot shards carry multi-command batches."""
    from rabia_tpu.apps.vector_kv import VectorShardedKV
    from rabia_tpu.apps.kvstore import encode_set_bin, shard_for_key
    from rabia_tpu.core.config import TcpNetworkConfig
    from rabia_tpu.core.network import ClusterConfig
    from rabia_tpu.core.types import NodeId
    from rabia_tpu.engine import RabiaEngine
    from rabia_tpu.net.tcp import TcpNetwork

    S, R = 16384, 5
    ids = [NodeId.from_int(i + 1) for i in range(R)]
    nets = [TcpNetwork(i, TcpNetworkConfig(bind_port=0)) for i in ids]
    for i in range(R):
        for j in range(R):
            if i != j:
                nets[i].add_peer(ids[j], "127.0.0.1", nets[j].port)
    engines, tasks = [], []
    for i, n in enumerate(ids):
        engines.append(
            RabiaEngine(
                ClusterConfig.new(n, ids),
                VectorShardedKV(S, capacity=1 << 18),
                nets[i],
                config=_cfg(S),
            )
        )
        tasks.append(asyncio.ensure_future(engines[-1].run()))
    _note_tick_path(engines)
    for _ in range(500):
        await asyncio.sleep(0.01)
        sts = [await e.get_statistics() for e in engines]
        if all(s.has_quorum for s in sts):
            break

    # Zipf key universe mapped to shards once; each cycle a shard's slot
    # carries however many hot keys hash into it (1..k)
    rng = np.random.default_rng(0)
    zipf_keys = [f"key{min(int(z), 99999)}" for z in rng.zipf(1.2, size=30000)]
    per_shard: dict[int, list[bytes]] = {}
    for k in zipf_keys:
        per_shard.setdefault(shard_for_key(k, S), []).append(
            encode_set_bin(k, "v")
        )
    default_op = [encode_set_bin("cold", "v")]

    def cmds(s: int) -> list[bytes]:
        return per_shard.get(s, default_op)[:32]

    lat: list[float] = []
    t0 = time.perf_counter()
    base, _ = await _committed(engines)
    acked = await _block_pump(engines, S, R, 8.0, cmds, lat=lat)
    top, _ = await _committed(engines)
    dt = time.perf_counter() - t0
    rate = (top - base) / dt
    await _stop(engines, tasks, nets)
    return _emit(
        "5:kvstore_5rep_16384shards_tcp_zipf",
        rate,
        "decisions/s",
        baselines,
        {
            "mode": "engine",
            "store": "vector_kv",
            "lane": "block",
            "transport": "native_tcp_loopback",
            "zipf_s": 1.2,
            "commands_acked": int(acked),
            "commands_per_sec": round(acked / dt, 1),
            **_lat_stats(lat),
        },
    )


async def config6_kvstore_tcp_runtime(baselines) -> None:
    """Config-3 geometry over the NATIVE TCP transport: kvstore, 5
    replicas, 4096 shards, block lane. This is the native engine
    runtime's home configuration — the GIL-free io/tick thread
    (native/runtime.cpp) engages automatically on C-transport clusters
    (RABIA_PY_RUNTIME=1 forces the asyncio orchestration for the
    before/after pair), so the r08 before/after comparison runs the
    SAME transport on both legs. The in-memory config 3 stays the
    r07-comparable line."""
    from rabia_tpu.apps import make_sharded_kv
    from rabia_tpu.apps.kvstore import encode_set_bin
    from rabia_tpu.core.network import ClusterConfig
    from rabia_tpu.core.types import NodeId
    from rabia_tpu.core.config import TcpNetworkConfig
    from rabia_tpu.engine import RabiaEngine
    from rabia_tpu.net.tcp import TcpNetwork

    S, R = 4096, 5
    ids = [NodeId.from_int(i + 1) for i in range(R)]
    nets = [TcpNetwork(i, TcpNetworkConfig(bind_port=0)) for i in ids]
    for i in range(R):
        for j in range(R):
            if i != j:
                nets[i].add_peer(ids[j], "127.0.0.1", nets[j].port)
    engines, tasks = [], []
    for i, n in enumerate(ids):
        engines.append(
            RabiaEngine(
                ClusterConfig.new(n, ids),
                make_sharded_kv(S)[0],
                nets[i],
                config=_cfg(S),
            )
        )
        tasks.append(asyncio.ensure_future(engines[-1].run()))
    _note_tick_path(engines)
    for _ in range(500):
        await asyncio.sleep(0.01)
        sts = [await e.get_statistics() for e in engines]
        if all(s.has_quorum for s in sts):
            break
    one_op = [[encode_set_bin(f"k{s}", "v")] for s in range(S)]
    lat: list[float] = []
    t0 = time.perf_counter()
    base, _ = await _committed(engines)
    await _block_pump(engines, S, R, 8.0, lambda s: one_op[s], lat=lat)
    top, _ = await _committed(engines)
    dt = time.perf_counter() - t0
    e0 = engines[0]
    rtm = (
        {
            k: v
            for k, v in e0._rtm.counters_dict().items()
            if k
            in (
                "waves_native",
                "waves_py",
                "slots_applied",
                "gil_handoffs",
                "frames_native",
                "frames_escalated",
                "ev_stalls",
            )
        }
        if e0._rtm is not None
        else None
    )
    await _stop(engines, tasks, nets)
    return _emit(
        "6:kvstore_5rep_4096shards_tcp_runtime",
        (top - base) / dt,
        "decisions/s",
        baselines,
        {
            "mode": "engine",
            "store": "kvstore_smr",
            "lane": "block",
            "transport": "native_tcp_loopback",
            "commands_per_slot": 1,
            **({"runtime_counters": rtm} if rtm else {}),
            **_lat_stats(lat),
        },
    )


_CONFIG_FNS = {
    1: lambda b: config1_counter(b),
    2: lambda b: config2_kvstore_64(b),
    3: lambda b: config3_kvstore_4096_batched(b),
    4: lambda b: config4_banking_crash(b),
    5: lambda b: config5_kvstore_tcp_zipf(b),
    # 6: the r08 native-runtime line (config-3 geometry over native TCP)
    6: lambda b: config6_kvstore_tcp_runtime(b),
}


def _aggregate(samples: list[dict]) -> dict:
    """Median ± IQR over repeated runs of ONE config (no headline
    backed by a single sample)."""
    import statistics

    vals = sorted(s["value"] for s in samples)
    agg = dict(samples[-1])
    agg["repeats"] = len(samples)
    agg["samples"] = [round(v, 1) for v in vals]
    med = vals[len(vals) // 2]
    if len(vals) >= 2:
        q1, med, q3 = statistics.quantiles(vals, n=4, method="inclusive")
        agg["iqr"] = [round(q1, 1), round(q3, 1)]
    agg["value"] = round(med, 1)
    if samples[-1].get("baseline_oracle_per_sec"):
        agg["vs_oracle"] = round(med / samples[-1]["baseline_oracle_per_sec"], 2)
    if samples[-1].get("baseline_cpu_engine_per_sec"):
        agg["vs_baseline"] = round(
            med / samples[-1]["baseline_cpu_engine_per_sec"], 2
        )
    for key in ("settle_p50_ms", "settle_p99_ms", "p50_latency_ms"):
        xs = sorted(
            s[key] for s in samples if s.get(key) is not None
        )
        if xs:
            agg[key] = xs[len(xs) // 2]
    return agg


def run_sweep(which=None, repeats: int = 1) -> list[dict]:
    """Run the 5-config sweep ``repeats`` times; returns one (aggregated)
    doc per config. Shared by the CLI below and ``bench.py --sweep``."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    import logging

    logging.disable(logging.WARNING)

    which = set(which or (1, 2, 3, 4, 5))
    baselines = {"oracle": cpu_oracle_baseline()}
    baselines["cpu_engine"] = asyncio.run(_cpu_engine_rate())
    print(
        json.dumps(
            {
                "metric": "baselines",
                "oracle_per_sec": round(baselines["oracle"], 1),
                "cpu_engine_per_sec": round(baselines["cpu_engine"], 1),
                "cpu_engine_config": "scalar lane, 4096 shards x 5 replicas, in-memory, kvstore",
            }
        )
    )
    per_config: dict[int, list[dict]] = {c: [] for c in sorted(which)}
    for r in range(max(1, repeats)):
        if repeats > 1:
            print(f"sweep: repeat {r + 1}/{repeats}", file=sys.stderr)
        for c in sorted(which):
            per_config[c].append(asyncio.run(_CONFIG_FNS[c](baselines)))
    out = []
    for c in sorted(which):
        doc = (
            _aggregate(per_config[c])
            if len(per_config[c]) > 1
            else per_config[c][0]
        )
        if len(per_config[c]) > 1:
            print(json.dumps(doc))  # the aggregated line (repeats mode)
        out.append(doc)
    _persist_sweep_obs(out)
    return out


def _persist_sweep_obs(docs: list[dict]) -> None:
    """Snapshot each config's metrics-registry context into
    benchmarks/results.json (key ``sweep_metrics``, latest run per
    config name), so BENCH rounds carry counter context — decisions,
    stale drops, out-pool hit rate — not just throughput."""
    path = Path(__file__).resolve().parent / "results.json"
    try:
        existing = json.loads(path.read_text()) if path.exists() else {}
    except (OSError, json.JSONDecodeError):
        existing = {}
    entry = existing.setdefault("sweep_metrics", {})
    for doc in docs:
        if doc.get("obs"):
            entry[doc["config"]] = {
                "value": doc.get("value"),
                "unit": doc.get("unit"),
                "tick_path": doc.get("tick_path"),
                **doc["obs"],
            }
    try:
        path.write_text(json.dumps(existing, indent=1))
    except OSError as e:  # read-only checkout: report, don't fail the run
        print(f"sweep: could not persist obs snapshot: {e}", file=sys.stderr)


def main() -> int:
    import argparse

    ap = argparse.ArgumentParser(description="BASELINE 5-config engine sweep")
    ap.add_argument("configs", nargs="*", type=int, help="subset (1-5)")
    ap.add_argument(
        "--repeats", type=int, default=1, metavar="N",
        help="run the sweep N times and report median ± IQR per config",
    )
    args = ap.parse_args()
    run_sweep(args.configs or None, repeats=args.repeats)
    return 0


if __name__ == "__main__":
    sys.exit(main())
