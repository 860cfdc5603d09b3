"""Benchmark the MeshEngine: the full SMR stack driven by the device-plane
collective kernel (SURVEY.md §5.8) — consensus + payload binding + state
machine apply + client futures, end to end.

Runs on whatever backend is live (the TPU through the chip tool; the
virtual CPU mesh in CI — every record carries its ``backend``) and
records decisions/s into ``results.json``. Usage::

    python benchmarks/mesh_engine_bench.py [--record]
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax
import numpy as np

from rabia_tpu.core.errors import RabiaError
from rabia_tpu.core.state_machine import InMemoryStateMachine
from rabia_tpu.parallel import MeshEngine, make_mesh


def bench_config(
    n_shards: int,
    n_replicas: int,
    window: int,
    waves: int,
    store: str = "inmem",
) -> dict:
    if store == "vector":
        from rabia_tpu.apps.kvstore import encode_set_bin
        from rabia_tpu.apps.vector_kv import VectorShardedKV

        factory = lambda: VectorShardedKV(n_shards, capacity=1 << 18)
        op = [encode_set_bin("k", "v")]
    else:
        factory = InMemoryStateMachine
        op = ["SET k v"]
    eng = MeshEngine(
        factory,
        n_shards=n_shards,
        n_replicas=n_replicas,
        mesh=make_mesh(),
        window=window,
    )
    # warm the jit cache (first compile is tens of seconds on TPU)
    for s in range(n_shards):
        eng.submit(op, s)
    eng.flush()
    t_compile = time.perf_counter()
    for _ in range(waves * window):
        for s in range(n_shards):
            eng.submit(op, s)
    t0 = time.perf_counter()
    applied = eng.flush(max_cycles=waves * 4)
    dt = time.perf_counter() - t0
    return {
        "shards": n_shards,
        "replicas": n_replicas,
        "window": window,
        "store": store,
        "applied": applied,
        "elapsed_s": round(dt, 4),
        "decisions_per_sec": round(applied / dt, 1),
        "enqueue_s": round(t0 - t_compile, 4),
        "cycles": eng.cycles,
    }


def bench_block_lane(
    n_shards: int, n_replicas: int, window: int, waves: int,
    device_store: bool = False,
) -> dict:
    """The bulk lane: full-width PayloadBlocks through submit_block —
    per-slot host overhead is a queue pop and a future index.
    ``device_store=True`` runs the device-resident KV lane (decide +
    apply fused on device, 12-byte readback per window). An incomplete
    drain raises (``flush``), and so does a device lane that demoted:
    a rate is only ever reported for the lane it names."""
    from rabia_tpu.apps.kvstore import encode_set_bin
    from rabia_tpu.apps.vector_kv import VectorShardedKV
    from rabia_tpu.core.blocks import build_block

    eng = MeshEngine(
        lambda: VectorShardedKV(n_shards, capacity=1 << 18),
        n_shards=n_shards,
        n_replicas=n_replicas,
        mesh=make_mesh(),
        window=window,
        device_store=device_store,
    )
    shards = list(range(n_shards))
    cmds = [[encode_set_bin(f"k{s}", "v")] for s in range(n_shards)]
    eng.submit_block(build_block(shards, cmds))
    eng.flush()  # compile
    blocks = [
        build_block(shards, cmds) for _ in range(waves * window)
    ]
    t_built = time.perf_counter()
    futs = [eng.submit_block(b) for b in blocks]
    t0 = time.perf_counter()
    applied = eng.flush(max_cycles=waves * 4)
    dt = time.perf_counter() - t0
    if not all(f.done() for f in futs):
        raise RabiaError("block futures unsettled after the drain")
    if device_store and not eng.device_lane_active:
        raise RabiaError("device lane demoted during the benchmark")
    return {
        "shards": n_shards,
        "replicas": n_replicas,
        "window": window,
        "lane": "block_device" if device_store else "block",
        "applied": applied,
        "elapsed_s": round(dt, 4),
        "decisions_per_sec": round(applied / dt, 1),
        "enqueue_s": round(t0 - t_built, 4),
        "cycles": eng.cycles,
    }


def bench_mixed_set_get(
    n_shards: int = 4096,
    n_replicas: int = 5,
    window: int = 64,
    reps: int = 12,
    set_waves: int = 64,
    get_waves: int = 8,
    read_lane: bool = False,
) -> dict:
    """Interleaved SET/GET workload through the device lane (the round-4
    weak spot: kind boundaries split the FIFO into window-per-run). The
    kind-masked mixed program now runs boundary-crossing windows at full
    width; this bench records the same 12×(64 SET + 8 GET) workload.
    One warmup rep compiles all three program signatures (pure SET,
    pure GET, mixed) outside the timed region."""
    from rabia_tpu.apps.kvstore import (
        KVOperation,
        KVOpType,
        encode_op_bin,
        encode_set_bin,
    )
    from rabia_tpu.apps.vector_kv import VectorShardedKV
    from rabia_tpu.core.blocks import build_block

    enc_get = lambda k: encode_op_bin(KVOperation(KVOpType.Get, k))
    shards = list(range(n_shards))
    set_cmds = [[encode_set_bin(f"k{s}", "v0")] for s in range(n_shards)]
    get_cmds = [[enc_get(f"k{s}")] for s in range(n_shards)]

    def one_rep():
        return [build_block(shards, set_cmds) for _ in range(set_waves)] + [
            build_block(shards, get_cmds) for _ in range(get_waves)
        ]

    eng = MeshEngine(
        lambda: VectorShardedKV(n_shards, capacity=1 << 18),
        n_shards=n_shards,
        n_replicas=n_replicas,
        mesh=make_mesh(),
        window=window,
        device_store=True,
        device_read_lane=read_lane,
    )
    for b in one_rep():  # warmup: compiles SET + mixed + GET programs
        eng.submit_block(b)
    eng.flush(max_cycles=400)
    assert eng._dev_active, "warmup demoted the device lane"
    rl0 = eng.read_lane_stats()
    blocks = []
    for _ in range(reps):
        blocks.extend(one_rep())
    futs = [eng.submit_block(b) for b in blocks]
    t0 = time.perf_counter()
    before = eng.decided_v1
    eng.flush(max_cycles=reps * (set_waves + get_waves) * 4)
    dt = time.perf_counter() - t0
    applied = eng.decided_v1 - before
    assert eng._dev_active, "mixed windows demoted the device lane"
    assert all(f.done() for f in futs)
    rl1 = eng.read_lane_stats()
    rl = {k: rl1[k] - rl0[k] for k in rl1}
    # with the read lane on, GETs never consume slots: decided_v1
    # counts SET decisions only, and total ops = decisions + probe
    # reads (same workload either way — the honest comparison axis)
    ops = applied + rl["probe"]
    return {
        "shards": n_shards,
        "replicas": n_replicas,
        "window": window,
        "read_lane": read_lane,
        "workload": (
            f"{reps} reps of {set_waves} SET waves + {get_waves} GET "
            "waves, full-width"
        ),
        "device_lane_decisions_per_sec": round(applied / dt, 1),
        "ops_per_sec": round(ops / dt, 1),
        "read_lane_deltas": rl,
        "elapsed_s": round(dt, 3),
        "cycles": eng.cycles,
        "note": (
            "kind-masked mixed windows: boundary-crossing FIFOs run "
            "full W-deep windows (one dispatch), GET planes download "
            "only for the waves that hold GETs; mixed windows PIPELINE "
            "(chained dispatch, worker-thread flags+meta fetch) like "
            "the pure-SET lane"
            + (
                "; read_lane=True skims GETs out pre-consensus into "
                "zero-slot lookup_only probe windows — the consensus "
                "stream dispatches SET-only windows"
                if read_lane
                else ""
            )
        ),
    }


def bench_del_heavy(
    n_shards: int = 4096,
    n_replicas: int = 5,
    window: int = 32,
    waves: int = 96,
) -> dict:
    """DEL-heavy device-lane workload: alternating full-width SET / DEL
    waves (every DEL finds its key, the worst case for the
    found-dependent version bump). Before pipelining, every DEL-bearing
    window drained the pipe and dispatched synchronously against the
    settled table. DEL windows now PIPELINE with settlement-time
    version derivation (the found bits already ride the meta plane), so
    the readback round trip overlaps the next window's pack like every
    other window kind."""
    from rabia_tpu.apps.kvstore import (
        KVOperation,
        KVOpType,
        encode_op_bin,
        encode_set_bin,
    )
    from rabia_tpu.apps.vector_kv import VectorShardedKV
    from rabia_tpu.core.blocks import build_block

    shards = list(range(n_shards))
    set_cmds = [[encode_set_bin(f"k{s}", "v0")] for s in range(n_shards)]
    del_cmds = [
        [encode_op_bin(KVOperation(KVOpType.Delete, f"k{s}"))]
        for s in range(n_shards)
    ]

    def stream(n_waves):
        return [
            build_block(shards, set_cmds if w % 2 == 0 else del_cmds)
            for w in range(n_waves)
        ]

    eng = MeshEngine(
        lambda: VectorShardedKV(n_shards, capacity=1 << 18),
        n_shards=n_shards,
        n_replicas=n_replicas,
        mesh=make_mesh(),
        window=window,
        device_store=True,
    )
    for b in stream(2 * window):  # warmup: compiles the mixed program
        eng.submit_block(b)
    eng.flush(max_cycles=400)
    assert eng._dev_active, "warmup demoted the device lane"
    futs = [eng.submit_block(b) for b in stream(waves)]
    t0 = time.perf_counter()
    before = eng.decided_v1
    eng.flush(max_cycles=waves * 4)
    dt = time.perf_counter() - t0
    applied = eng.decided_v1 - before
    assert eng._dev_active, "DEL windows demoted the device lane"
    assert all(f.done() for f in futs)
    return {
        "shards": n_shards,
        "replicas": n_replicas,
        "window": window,
        "workload": f"{waves} alternating full-width SET / DEL waves",
        "decisions_per_sec": round(applied / dt, 1),
        "elapsed_s": round(dt, 3),
        "cycles": eng.cycles,
        "vs_r05_sync_del": round(applied / dt / 82_048, 2),
        "note": (
            "DEL-bearing windows pipeline with DEFERRED version "
            "derivation: the found-dependent shard-version bump is "
            "computed at settlement from the meta readback (which DEL "
            "waves already ride), so the dispatch chains like any "
            "other window instead of draining the pipe — conformance "
            "pinned in tests/test_device_kv.py "
            "(test_del_windows_pipeline_with_deferred_versions)"
        ),
    }


def bench_get_windows(
    n_shards: int = 4096,
    n_replicas: int = 5,
    window: int = 64,
    waves: int = 192,
    read_lane: bool = False,
) -> dict:
    """GET-only windows through the device lane. The round-4 read path
    downloaded ~70 bytes/op of found/ver/value planes; the meta-only
    read path downloads ~5 bytes/op and resolves values from the
    host-retained SET segments."""
    from rabia_tpu.apps.kvstore import (
        KVOperation,
        KVOpType,
        encode_op_bin,
        encode_set_bin,
    )
    from rabia_tpu.apps.vector_kv import VectorShardedKV
    from rabia_tpu.core.blocks import build_block

    enc_get = lambda k: encode_op_bin(KVOperation(KVOpType.Get, k))
    shards = list(range(n_shards))
    eng = MeshEngine(
        lambda: VectorShardedKV(n_shards, capacity=1 << 18),
        n_shards=n_shards,
        n_replicas=n_replicas,
        mesh=make_mesh(),
        window=window,
        device_store=True,
        device_read_lane=read_lane,
    )
    set_cmds = [[encode_set_bin(f"k{s}", f"v{s % 7}")] for s in range(n_shards)]
    get_cmds = [[enc_get(f"k{s}")] for s in range(n_shards)]
    for _ in range(2):  # populate + compile SET program
        eng.submit_block(build_block(shards, set_cmds))
    eng.flush()
    eng.submit_block(build_block(shards, get_cmds))  # compile GET program
    eng.flush()
    rl0 = eng.read_lane_stats()
    blocks = [build_block(shards, get_cmds) for _ in range(waves)]
    futs = [eng.submit_block(b) for b in blocks]
    t0 = time.perf_counter()
    eng.flush(max_cycles=waves * 4)
    dt = time.perf_counter() - t0
    assert eng._dev_active, "GET windows demoted the lane"
    assert all(f.done() for f in futs)
    # materialize a sample of responses so lazy framing is honest work
    sample = [bytes(g[0]) for g in futs[-1].result()[:64]]
    assert all(s for s in sample)
    rl1 = eng.read_lane_stats()
    return {
        "shards": n_shards,
        "replicas": n_replicas,
        "window": window,
        "waves": waves,
        "read_lane": read_lane,
        "read_lane_deltas": {k: rl1[k] - rl0[k] for k in rl1},
        "reads_per_sec": round(waves * n_shards / dt, 1),
        "elapsed_s": round(dt, 3),
        "meta_bytes_per_op": 5,
        "value_plane_bytes_per_op": 73,
        "note": (
            "meta-only GET readback (found bits + version words); value "
            "bytes resolve from host-retained SET segments keyed by "
            "(shard, version) — the value planes are not downloaded "
            "in the steady state; GET windows PIPELINE (chained "
            "lookup dispatch, worker-thread meta fetch)"
        ),
    }


def bench_latency_governor(
    n_shards: int,
    n_replicas: int,
    targets_ms: list,
    seconds_per: float = 6.0,
    device_store: bool = False,
) -> dict:
    """Throughput-vs-p99 under the window governor.

    For each latency target, a governed engine
    (``MeshEngine(latency_target_ms=...)``) runs the block lane under
    saturating demand (the feed keeps ~2 windows of blocks queued, so
    the governor is free to grow as well as shrink); after the run the
    achieved per-window p50/p99 and throughput are recorded along with
    where the governor parked W. This replaces the manual
    window_sweep_block_lane knob: pick a latency target, get the window.
    """
    from rabia_tpu.apps.kvstore import encode_set_bin
    from rabia_tpu.apps.vector_kv import VectorShardedKV
    from rabia_tpu.core.blocks import build_block

    shards = list(range(n_shards))
    cmds = [[encode_set_bin(f"k{s}", "v")] for s in range(n_shards)]
    out = {}
    for t_ms in targets_ms:
        eng = MeshEngine(
            lambda: VectorShardedKV(n_shards, capacity=1 << 18),
            n_shards=n_shards,
            n_replicas=n_replicas,
            mesh=make_mesh(),
            window=16,
            device_store=device_store,
            latency_target_ms=t_ms,
            max_window=256,
        )
        eng.submit_block(build_block(shards, cmds))
        eng.flush()  # compile the initial window size
        # prebuilt cycled pool: building 2*W full-width blocks in Python
        # between cycles would measure the FEED, not the engine (at
        # W=128 the per-cycle build cost exceeded the window itself) —
        # same prebuild policy as bench_block_lane
        pool = [build_block(shards, cmds) for _ in range(512)]
        pool_i = 0
        samples = []
        applied = 0
        settled_at = 0  # sample index of the last governor resize
        t0 = time.perf_counter()
        deadline = t0 + seconds_per
        while time.perf_counter() < deadline or len(samples) - settled_at < 8:
            if time.perf_counter() > t0 + 4 * seconds_per:
                break  # hard cap: never-settling targets still report
            while len(eng._full_blocks) < 2 * eng.window:
                eng.submit_block(pool[pool_i % len(pool)])
                pool_i += 1
            resizes = eng.window_resizes
            c0 = time.perf_counter()
            applied += eng.run_cycle()
            samples.append((time.perf_counter() - c0) * 1e3)
            if eng.window_resizes != resizes:
                # +1: the next cycle pays the new size's jit compile —
                # the engine leaves it untimed (_lat_skip) and so must
                # the recorded tail, or p99 reports a compile
                settled_at = len(samples) + 1
        dt = time.perf_counter() - t0
        # stats over the settled tail: windows run at the final W only
        tail = samples[settled_at:]
        a = np.asarray(tail if tail else samples)
        gstats = eng.governor_stats()
        out[f"target_{t_ms:g}ms"] = {
            "window": eng.window,
            "resizes": eng.window_resizes,
            "windows_timed": len(samples),
            "settled_windows": len(tail),
            # empty tail = the hard cap fired mid-resize; stats then
            # cover mixed window sizes and say so
            "mixed_sizes": not tail,
            "p50_ms": round(float(np.percentile(a, 50)), 2),
            "p99_ms": round(float(np.percentile(a, 99)), 2),
            # aggregate includes the one-off jit compile of every ladder
            # size the governor walked through (seconds each, paid once
            # per process); settled_decisions_per_sec is the steady
            # state at the final W — what a long-running deployment
            # actually sustains
            "decisions_per_sec": round(applied / dt, 1),
            "settled_decisions_per_sec": (
                round(
                    len(tail)
                    * eng.window
                    * n_shards
                    / (float(np.sum(a)) / 1e3),
                    1,
                )
                if tail
                else None
            ),
            # the governor's own view: its p99 estimate and whether it
            # declared the target below the hardware floor
            "governor_p99_ms": gstats["p99_ms"],
            "governor_p99_decision_ms": gstats["p99_decision_ms"],
            "unachievable": gstats["unachievable"],
            "floor_ms": gstats["floor_ms"],
            # client-observed dispatch->settle p99 (governed mode runs
            # the pipe at depth 1, so this tracks ~window time + the
            # next cycle's pack; None when the lane is demoted/absent)
            "inflight": gstats["inflight"],
            "settle_p99_ms": gstats["settle_p99_ms"],
        }
        print(
            f"  governor target {t_ms}ms -> W={eng.window} "
            f"p50={out[f'target_{t_ms:g}ms']['p50_ms']}ms "
            f"p99={out[f'target_{t_ms:g}ms']['p99_ms']}ms "
            f"{out[f'target_{t_ms:g}ms']['decisions_per_sec']} dec/s"
        )
    return out


def _conformance_point(n_devices: int, n_shards: int) -> bool:
    """Device-lane vs host-store conformance on an ``n_devices`` mesh.

    The same deterministic SET+GET workload runs through a device-store
    engine sharded over the mesh and a host-only engine; final store
    content, versions, and response frames must match byte-for-byte
    (the tests/test_device_kv.py gate, here re-checked at every mesh
    width the scaling table reports).
    """
    from rabia_tpu.apps.kvstore import (
        KVOperation,
        KVOpType,
        encode_op_bin,
        encode_set_bin,
    )
    from rabia_tpu.apps.vector_kv import VectorShardedKV
    from rabia_tpu.core.blocks import build_block

    mesh = make_mesh(jax.devices()[:n_devices])
    shards = list(range(n_shards))
    enc_get = lambda k: encode_op_bin(KVOperation(KVOpType.Get, k))

    enc_del = lambda k: encode_op_bin(KVOperation.delete(k))

    def blocks():
        out = []
        for wave in range(6):
            cmds = [
                [encode_set_bin(f"k{s % 5}", f"v{wave}.{s % 3}")]
                for s in range(n_shards)
            ]
            out.append(build_block(shards, cmds))
        # DEL waves exercise the deferred-version pipeline at this mesh
        # width (found AND not-found), then a re-SET and the read wave
        out.append(
            build_block(shards, [[enc_del(f"k{s % 5}")] for s in range(n_shards)])
        )
        out.append(
            build_block(
                shards, [[enc_del("absent")] for _ in range(n_shards)]
            )
        )
        out.append(
            build_block(
                shards,
                [[encode_set_bin(f"k{s % 5}", "post-del")] for s in range(n_shards)],
            )
        )
        out.append(
            build_block(shards, [[enc_get(f"k{s % 5}")] for s in range(n_shards)])
        )
        return out

    def run(device: bool):
        eng = MeshEngine(
            lambda: VectorShardedKV(n_shards, capacity=1 << 12),
            n_shards=n_shards,
            n_replicas=3,
            mesh=mesh,
            window=4,
            device_store=device,
        )
        futs = [eng.submit_block(b) for b in blocks()]
        eng.flush(max_cycles=200)
        if device:
            # sync the device table down so the host SMs hold final state
            eng._demote_device_store()
            eng.close()
        frames = [
            bytes(f)
            for fut in futs
            for grp in fut.result()
            for f in grp
        ]
        st = eng.sms[0].store
        used = np.nonzero(st.state == 1)[0]
        content = {}
        for slot in used.tolist():
            key = (
                st.key_lanes[slot]
                .view(np.uint8)[: int(st.key_len[slot])]
                .tobytes()
            )
            content[(int(st.shard_col[slot]), key)] = (
                eng.sms[0].store._value_at(slot),
                int(st.version[slot]),
            )
        return frames, content

    dev_frames, dev_content = run(device=True)
    host_frames, host_content = run(device=False)
    return dev_frames == host_frames and dev_content == host_content


def bench_weak_scaling_point(
    n_devices: int,
    per_device_shards: int = 512,
    n_replicas: int = 5,
    window: int = 32,
    waves: int = 4,
) -> dict:
    """One weak-scaling row: device-store block lane on the first
    ``n_devices`` devices, shard count proportional to mesh width
    (fixed per-device work — the multi-chip readiness shape).
    Conformance re-checked at this width."""
    from rabia_tpu.apps.kvstore import encode_set_bin
    from rabia_tpu.apps.vector_kv import VectorShardedKV
    from rabia_tpu.core.blocks import build_block

    n_shards = per_device_shards * n_devices
    mesh = make_mesh(jax.devices()[:n_devices])
    eng = MeshEngine(
        lambda: VectorShardedKV(n_shards, capacity=1 << 16),
        n_shards=n_shards,
        n_replicas=n_replicas,
        mesh=mesh,
        window=window,
        device_store=True,
    )
    shards = list(range(n_shards))
    cmds = [[encode_set_bin(f"k{s}", "v")] for s in range(n_shards)]
    eng.submit_block(build_block(shards, cmds))
    eng.flush()  # compile at this mesh width
    blocks = [build_block(shards, cmds) for _ in range(waves * window)]
    futs = [eng.submit_block(b) for b in blocks]
    t0 = time.perf_counter()
    applied = eng.flush(max_cycles=waves * 6)
    dt = time.perf_counter() - t0
    assert all(f.done() for f in futs)
    assert eng._dev_active, "device lane demoted during the scaling bench"
    eng.close()
    return {
        "devices": n_devices,
        "shards": n_shards,
        "per_device_shards": per_device_shards,
        "replicas": n_replicas,
        "window": window,
        "applied": applied,
        "elapsed_s": round(dt, 4),
        "decisions_per_sec": round(applied / dt, 1),
        "decisions_per_sec_per_device": round(applied / dt / n_devices, 1),
        "conformant": _conformance_point(n_devices, 16 * n_devices),
    }


def _spawn_virtual_point(n_devices: int, per_device_shards: int) -> dict:
    """Run one scaling row in a subprocess forced onto ``n_devices``
    virtual CPU devices (the sanctioned no-hardware validation mode)."""
    import os
    import subprocess

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n_devices}"
    ).strip()
    proc = subprocess.run(
        [
            sys.executable,
            str(Path(__file__).resolve()),
            "--devices-worker",
            str(n_devices),
            "--per-device-shards",
            str(per_device_shards),
        ],
        env=env,
        capture_output=True,
        text=True,
        timeout=900,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"virtual {n_devices}-device worker failed:\n{proc.stderr[-2000:]}"
        )
    return json.loads(proc.stdout.strip().splitlines()[-1])


def run_weak_scaling(max_devices: int, per_device_shards: int = 512) -> dict:
    """The multi-chip readiness table: mesh widths 1,2,4,...,max_devices,
    fixed per-device shard count. On a host whose live backend already
    exposes enough devices the rows run in-process (REAL numbers); any
    wider row falls back to a virtual-CPU-mesh subprocess (labeled
    ``virtual`` — validates sharding + conformance, not throughput).
    The day multi-chip hardware exists, the same command produces the
    real table."""
    live = len(jax.devices())
    backend = jax.devices()[0].platform
    widths = []
    d = 1
    while d <= max_devices:
        widths.append(d)
        d *= 2
    rows = []
    for d in widths:
        if d <= live:
            row = bench_weak_scaling_point(d, per_device_shards)
            row["backend"] = backend
            row["virtual"] = backend == "cpu"
        else:
            row = _spawn_virtual_point(d, per_device_shards)
            row["backend"] = "cpu"
            row["virtual"] = True
        rows.append(row)
        print(
            f"  devices={d} shards={row['shards']} -> "
            f"{row['decisions_per_sec']} dec/s "
            f"({row['decisions_per_sec_per_device']}/device, "
            f"{'virtual' if row['virtual'] else backend}, "
            f"conformant={row['conformant']})"
        )
    return {
        "note": (
            "weak scaling of the device-store block lane over mesh width; "
            "per-device shard count fixed. Rows marked virtual ran on a "
            "forced-CPU virtual mesh: they validate that the sharded "
            "program compiles, runs, and conforms at that width — their "
            "throughput is host-CPU-bound, NOT a hardware number."
        ),
        "per_device_shards": per_device_shards,
        "rows": rows,
    }


def main() -> None:
    from rabia_tpu.core.compile_cache import place_compile_cache

    place_compile_cache()
    if "--devices-worker" in sys.argv:
        # a virtual-mesh row: _spawn_virtual_point started this process
        # with JAX_PLATFORMS=cpu assigned (the parent may hold the chip)
        d = int(sys.argv[sys.argv.index("--devices-worker") + 1])
        pds = (
            int(sys.argv[sys.argv.index("--per-device-shards") + 1])
            if "--per-device-shards" in sys.argv
            else 512
        )
        assert len(jax.devices()) >= d, (
            f"worker wanted {d} devices, backend has {len(jax.devices())}"
        )
        print(json.dumps(bench_weak_scaling_point(d, pds)))
        return

    if "--devices" in sys.argv:
        n = int(sys.argv[sys.argv.index("--devices") + 1])
        print(f"weak-scaling table up to {n} devices:")
        out = run_weak_scaling(n)
        if "--record" in sys.argv:
            path = Path(__file__).parent / "results.json"
            doc = json.loads(path.read_text()) if path.exists() else {}
            doc["mesh_engine_weak_scaling_r05"] = out
            path.write_text(json.dumps(doc, indent=1))
            print("recorded -> results.json mesh_engine_weak_scaling_r05")
        return

    if "--read-lane-only" in sys.argv:
        # device read-index lane A/B: the same mixed workload with GETs
        # riding consensus slots (before) vs skimmed into zero-slot
        # lookup_only probe windows (after), plus the GET-heavy mix and
        # the pure-GET stream through the probe path. Records a
        # same-host pair under mesh_engine_r17.
        backend = jax.devices()[0].platform
        off = bench_mixed_set_get(read_lane=False)
        print("mixed lane-off ->", off["device_lane_decisions_per_sec"],
              "dec/s,", off["ops_per_sec"], "ops/s")
        on = bench_mixed_set_get(read_lane=True)
        print("mixed lane-on  ->", on["device_lane_decisions_per_sec"],
              "dec/s,", on["ops_per_sec"], "ops/s")
        heavy = bench_mixed_set_get(
            reps=12, set_waves=8, get_waves=64, read_lane=True
        )
        print("get-heavy lane-on ->", heavy["ops_per_sec"], "ops/s")
        getw = bench_get_windows(read_lane=True)
        print("pure-GET probe ->", getw["reads_per_sec"], "reads/s")
        assert on["read_lane_deltas"]["slot"] == 0, (
            "read lane on: GETs still consumed consensus slots"
        )
        rec = {
            "backend": backend,
            "devices": len(jax.devices()),
            "mixed_read_lane_off": off,
            "mixed_read_lane_on": on,
            "mixed_get_heavy_read_lane_on": heavy,
            "get_windows_probe_path": getw,
        }
        if "--record" in sys.argv:
            path = Path(__file__).parent / "results.json"
            doc = json.loads(path.read_text()) if path.exists() else {}
            sect = doc.setdefault("mesh_engine_r17", {})
            key = (
                "read_lane_ab_cpu" if backend == "cpu" else "read_lane_ab"
            )
            sect[key] = rec
            path.write_text(json.dumps(doc, indent=1))
            print(f"recorded -> results.json mesh_engine_r17.{key}")
        return

    if "--read-smoke" in sys.argv:
        # CI cell: tiny GET/mixed windows on the CPU backend; asserts
        # the read lane actually ENGAGES (probe > 0, zero slot-GETs —
        # the --require-plane analog for the read path) and writes the
        # record for artifact upload via --out.
        rec = {
            "backend": jax.devices()[0].platform,
            "devices": len(jax.devices()),
            "mixed": bench_mixed_set_get(
                n_shards=64, n_replicas=3, window=8, reps=2,
                set_waves=8, get_waves=8, read_lane=True,
            ),
            "get_windows": bench_get_windows(
                n_shards=64, n_replicas=3, window=8, waves=16,
                read_lane=True,
            ),
        }
        for name in ("mixed", "get_windows"):
            d = rec[name]["read_lane_deltas"]
            assert d["probe"] > 0, f"{name}: read lane never engaged"
            assert d["slot"] == 0, (
                f"{name}: GETs consumed consensus slots with the lane on"
            )
        covered = rec["mixed"]["read_lane_deltas"]["probe"]
        total_gets = covered + rec["mixed"]["read_lane_deltas"]["slot"]
        rec["off_consensus_fraction"] = covered / max(1, total_gets)
        print(
            "read-smoke OK:",
            rec["mixed"]["ops_per_sec"], "mixed ops/s,",
            rec["get_windows"]["reads_per_sec"], "reads/s,",
            f"{rec['off_consensus_fraction']:.0%} of GETs off-consensus",
        )
        if "--out" in sys.argv:
            out_path = Path(sys.argv[sys.argv.index("--out") + 1])
            out_path.write_text(json.dumps(rec, indent=1))
            print("wrote ->", out_path)
        return

    if "--mixed-only" in sys.argv:
        # re-measure the interleaved + GET-window lanes (a device-lane
        # pipelining change doesn't require re-running the full bench)
        mixed = bench_mixed_set_get()
        print("mixed ->", mixed["device_lane_decisions_per_sec"], "dec/s")
        getw = bench_get_windows()
        print("get ->", getw["reads_per_sec"], "reads/s")
        if "--record" in sys.argv:
            path = Path(__file__).parent / "results.json"
            doc = json.loads(path.read_text()) if path.exists() else {}
            rec = doc.setdefault("mesh_engine_r05", {})
            rec["mixed_set_get_device_lane"] = mixed
            rec["get_windows_device_lane"] = getw
            path.write_text(json.dumps(doc, indent=1))
            print("recorded -> results.json mesh_engine_r05")
        return

    if "--del-only" in sys.argv:
        # re-measure the DEL-heavy lane (pipelined DEL windows)
        rec = bench_del_heavy()
        print("del-heavy ->", rec["decisions_per_sec"], "dec/s")
        if "--record" in sys.argv:
            path = Path(__file__).parent / "results.json"
            doc = json.loads(path.read_text()) if path.exists() else {}
            sect = doc.setdefault("mesh_engine_r05", {})
            prev = sect.get("del_heavy_device_lane", {})
            # keep the run history across re-records (medians live there)
            rec["runs_decisions_per_sec"] = prev.get(
                "runs_decisions_per_sec", []
            ) + [rec["decisions_per_sec"]]
            sect["del_heavy_device_lane"] = rec
            path.write_text(json.dumps(doc, indent=1))
            print("recorded -> results.json mesh_engine_r05")
        return

    if "--governor-only" in sys.argv:
        # re-measure just the governor sweep (it owns its own engines);
        # merged into the round record so a control-loop change doesn't
        # require re-running the full mesh bench
        print("latency governor sweep (block lane, 1024 shards x 3):")
        sweep = bench_latency_governor(1024, 3, [20.0, 60.0, 250.0, 1000.0])
        print("governed DEVICE lane point (settle-latency stats live):")
        dev_point = bench_latency_governor(
            1024, 3, [250.0], device_store=True
        )
        if "--record" in sys.argv:
            path = Path(__file__).parent / "results.json"
            doc = json.loads(path.read_text()) if path.exists() else {}
            sect = doc.setdefault("mesh_engine_r05", {})
            sect["latency_governor_sweep"] = sweep
            sect["latency_governor_device_point"] = dev_point
            path.write_text(json.dumps(doc, indent=1))
            print("recorded -> results.json mesh_engine_r05")
        return

    backend = jax.devices()[0].platform
    out = {
        "note": (
            "MeshEngine end-to-end: consensus via MeshPhaseKernel.slot_window "
            "(one dispatch per W-slot window) + host apply to R replica SMs "
            "+ future settlement. decisions_per_sec counts APPLIED batches."
        ),
        "backend": backend,
        "devices": len(jax.devices()),
    }
    for name, (S, R, W, waves, store) in {
        "s256_r3_w16": (256, 3, 16, 8, "inmem"),
        "s1024_r3_w16": (1024, 3, 16, 8, "inmem"),
        "s4096_r3_w16": (4096, 3, 16, 4, "inmem"),
        "s4096_r5_w16_vector": (4096, 5, 16, 4, "vector"),
    }.items():
        out[name] = bench_config(S, R, W, waves, store)
        print(name, "->", out[name]["decisions_per_sec"], "decisions/s")
    out["s4096_r5_w16_block_lane"] = bench_block_lane(4096, 5, 16, 4)
    print(
        "s4096_r5_w16_block_lane ->",
        out["s4096_r5_w16_block_lane"]["decisions_per_sec"],
        "decisions/s",
    )
    for name, (W, waves) in {
        "s4096_r5_w64_device_store": (64, 4),
        "s4096_r5_w128_device_store": (128, 4),
    }.items():
        out[name] = bench_block_lane(4096, 5, W, waves, device_store=True)
        print(name, "->", out[name]["decisions_per_sec"], "decisions/s")

    print("latency governor sweep (block lane, 1024 shards x 3):")
    out["latency_governor_sweep"] = bench_latency_governor(
        1024, 3, [20.0, 60.0, 250.0, 1000.0]
    )

    if "--record" in sys.argv:
        path = Path(__file__).parent / "results.json"
        doc = json.loads(path.read_text()) if path.exists() else {}
        doc["mesh_engine_r05"] = {**doc.get("mesh_engine_r05", {}), **out}
        path.write_text(json.dumps(doc, indent=1))
        print("recorded -> results.json mesh_engine_r05")


if __name__ == "__main__":
    main()
