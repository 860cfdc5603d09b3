"""Headline benchmark: consensus decisions/sec, device kernel vs CPU oracle.

Workload (BASELINE north star): 4096 concurrent consensus instances
(kvstore shards) × 5 replicas, deciding consecutive slots with the batched
weak-MVC kernel — whole slots scanned on device with no host round-trips
(`ClusterKernel.slot_pipeline`). Baseline: the scalar weak-MVC oracle (the
reference architecture's one-instance-at-a-time execution model) measured
on this host's CPU.

Prints exactly ONE JSON line:
  {"metric": "decisions_per_sec", "value": N, "unit": "decisions/s",
   "vs_baseline": ratio, ...}
"""

from __future__ import annotations

import json
import os
import sys
import time


def _cpu_oracle_rate(n_replicas: int, sample_slots: int = 150) -> float:
    """Decisions/sec of the scalar oracle (one instance at a time)."""
    from rabia_tpu.core.oracle import WeakMVCOracle
    from rabia_tpu.core.types import V1

    t0 = time.perf_counter()
    done = 0
    for s in range(sample_slots):
        oracle = WeakMVCOracle(
            n_replicas, [V1] * n_replicas, coin=lambda p: V1
        )
        for _ in range(64):
            oracle.step()
            if oracle.decided_value is not None:
                break
        done += 1
    dt = time.perf_counter() - t0
    return done / dt


def _measure_once() -> tuple[int, dict | None]:
    """One full scenario pass. Returns (exit_code, result_dict).

    Every leg is fatal: a kernel that fails to build or run, an engine
    leg that demotes or under-drains, raises and fails the run (there
    is no skip-and-continue and no smaller headline to fall back to).
    Device metrics are refused off the TPU."""
    shards = int(os.environ.get("BENCH_SHARDS", 4096))
    replicas = int(os.environ.get("BENCH_REPLICAS", 5))
    # slots per dispatch = the device pipeline depth; a deeper window
    # spreads the fixed per-dispatch cost over more decisions
    # (benchmarks/roofline.py t_sweep; the depth is not yet re-measured
    # on the attached chip)
    slots = int(os.environ.get("BENCH_SLOTS", 32768))
    reps = int(os.environ.get("BENCH_REPS", 4))
    # windows per timed chain: the production engine pipelines windows
    # (dispatch before the previous readback, parallel/mesh_engine.py),
    # so throughput is measured as a chain of back-to-back dispatches
    # over alternating buffers with ONE readback at the end — a single
    # dispatch+sync would time the host round trip with the kernel.
    # The chain length is not yet re-measured on the attached chip.
    chain = int(os.environ.get("BENCH_CHAIN", 48))

    import jax
    import jax.numpy as jnp
    import numpy as np

    from rabia_tpu.core.compile_cache import place_compile_cache
    from rabia_tpu.core.types import V1
    from rabia_tpu.kernel import ClusterKernel, packed_window

    dev0 = jax.devices()[0]
    if dev0.platform != "tpu":
        print(
            f"bench: backend is {dev0.platform!r}, not the TPU — refusing "
            "to report device metrics (run on the chip; the CPU engine "
            "sweep is `bench.py --sweep`)",
            file=sys.stderr,
        )
        return 2, None
    place_compile_cache()
    kernel = ClusterKernel(shards, replicas, seed=0)
    scan_slots = min(slots, 8192)  # scan path: compile time grows with T
    votes = jnp.full((scan_slots, shards, replicas), V1, jnp.int8)
    alive = jnp.ones((shards, replicas), bool)

    # warmup / compile
    decided, _ = kernel.slot_pipeline(votes, alive, scan_slots)
    decided.block_until_ready()
    assert np.all(np.asarray(decided) == V1)

    best = 0.0
    for _ in range(reps):
        t0 = time.perf_counter()
        decided, _ = kernel.slot_pipeline(votes, alive, scan_slots)
        decided.block_until_ready()
        dt = time.perf_counter() - t0
        best = max(best, shards * scan_slots / dt)
    scan_rate = best
    kernel_name = "slot_pipeline_scan"

    # the fused (Pallas) fault-free window on replica-major votes —
    # bit-identical to the scanned machinery (conformance-gated in
    # tests/test_kernel.py and on the chip by chip_smoke.py), measured
    # pipelined. Two distinct buffers are cycled through the chain so no
    # layer can collapse repeated dispatches.
    alive_rm = jnp.ones((replicas, shards), bool)
    votes_rm = [
        jnp.full((replicas, slots, shards), V1, jnp.int8),
        jnp.full((replicas, slots, shards), V1, jnp.int8),
    ]
    fused_d, _ = kernel.slot_pipeline_fused_rmajor(
        votes_rm[0], alive_rm, slots
    )
    if not bool(np.all(np.asarray(fused_d) == V1)):
        print("bench: FUSED KERNEL DECISIONS DIVERGE", file=sys.stderr)
        return 1, None
    fused_rate = 0.0
    for _ in range(reps):
        t0 = time.perf_counter()
        for i in range(chain):
            # want_phase=False: the phase plane is derivable (0 iff
            # decided) and nothing reads it here — and with up to
            # `chain` output sets in flight, the dead i32 planes would
            # dominate HBM residency
            d = kernel.slot_pipeline_fused_rmajor(
                votes_rm[i % 2], alive_rm, slots, want_phase=False
            )
        # one tiny readback forces the whole in-order chain
        np.asarray(d[0, :8])
        dt = time.perf_counter() - t0
        fused_rate = max(fused_rate, chain * shards * slots / dt)
    if not bool(np.all(np.asarray(d) == V1)):
        print("bench: FUSED KERNEL DECISIONS DIVERGE", file=sys.stderr)
        return 1, None
    del votes_rm, fused_d
    if fused_rate > best:
        best = fused_rate
        kernel_name = "pallas_fused_window_rmajor"

    # the packed-vote window (kernel/packed_window.py): 2-bit codes, 16
    # votes per u32 word, tallied with word-wise bit arithmetic — 1.5
    # bytes/decision instead of 6, and windows go 4x deeper in the same
    # memory. Conformance-gated in tests/test_packed_window.py; the
    # producer packs once outside the timed chain (pack_codes), same
    # policy as the prebuilt i8 planes. The depth/chain defaults are not
    # yet re-measured on the attached chip; the chain default scales
    # with BENCH_CHAIN so smoke runs (e.g. BENCH_CHAIN=4) stay bounded.
    packed_slots = int(os.environ.get("BENCH_SLOTS_PACKED", 393216))
    packed_chain = int(
        os.environ.get("BENCH_CHAIN_PACKED", 8 * chain // 3)
    )
    # pack in T-chunks: packing the full window in one shot would
    # materialize a u32 convert of the 4x-larger i8 plane (~32GB at
    # the default depth — over HBM); chunking bounds the transient
    step = min(packed_slots, 16384)
    parts = []
    for t_at in range(0, packed_slots, step):
        v8 = jnp.full(
            (replicas, min(step, packed_slots - t_at), shards),
            V1,
            jnp.int8,
        )
        parts.append(packed_window.pack_codes(v8))
        del v8
    p = jnp.concatenate(parts, axis=1)
    p.block_until_ready()
    del parts
    # second chain buffer: a device copy (defeats aliasing, skips a
    # second full pack pass)
    packed = [p, (p + jnp.uint32(0)).block_until_ready()]
    alive_p = packed_window.pack_alive(alive_rm)
    # expected decision row for a unanimous-V1 window: V1 at every
    # real lane, ABSENT at padding lanes — checked ON DEVICE (one bool
    # readback, not a multi-hundred-MB plane)
    expected_row = packed_window.pack_codes(
        jnp.full((shards,), V1, jnp.int8)
    )
    d = kernel.slot_pipeline_fused_packed(packed[0], alive_p, packed_slots)
    if not bool(jnp.all(d == expected_row[None, :])):
        print("bench: PACKED KERNEL DECISIONS DIVERGE", file=sys.stderr)
        return 1, None
    packed_rate = 0.0
    for _ in range(reps):
        t0 = time.perf_counter()
        for i in range(packed_chain):
            d = kernel.slot_pipeline_fused_packed(
                packed[i % 2], alive_p, packed_slots
            )
        np.asarray(d[0, :8])
        dt = time.perf_counter() - t0
        packed_rate = max(
            packed_rate, packed_chain * shards * packed_slots / dt
        )
    if not bool(jnp.all(d == expected_row[None, :])):
        print("bench: PACKED KERNEL DECISIONS DIVERGE", file=sys.stderr)
        return 1, None
    del packed, p, d
    if packed_rate > best:
        best = packed_rate
        kernel_name = "packed_window_rmajor_xla"

    cpu_rate = _cpu_oracle_rate(replicas)

    # Engine-level pairing (the BASELINE.json north-star metric): the full
    # SMR stack on the device plane (MeshEngine: consensus + apply +
    # futures) against the CPU scalar-lane ENGINE. Kernel-vs-oracle and
    # engine-vs-engine are different units; both are reported.
    eng_S, eng_R = min(shards, 4096), replicas
    engine_rate = _mesh_engine_rate(eng_S, eng_R)
    cpu_engine_rate = _cpu_engine_rate_quick(eng_S, eng_R)

    out = {
        "metric": "decisions_per_sec",
        "value": round(best, 1),
        "unit": "decisions/s",
        "device": {
            "platform": dev0.platform,
            "kind": dev0.device_kind,
            "count": len(jax.devices()),
        },
        "vs_baseline": round(best / cpu_rate, 2),
        "vs_oracle": round(best / cpu_rate, 2),
        # scan-vs-oracle keeps round-over-round comparisons on the same
        # basis (the scan executes the full round machinery; the fused
        # headline is its proven closed-form collapse)
        "vs_oracle_scan": round(scan_rate / cpu_rate, 2),
        "baseline_cpu_oracle_per_sec": round(cpu_rate, 1),
        "scan_decisions_per_sec": round(scan_rate, 1),
        "fused_decisions_per_sec": round(fused_rate, 1),
        "packed_decisions_per_sec": round(packed_rate, 1),
        "config": {
            "shards": shards,
            "replicas": replicas,
            # report the geometry the adopted headline actually ran at
            "slots_per_dispatch": (
                packed_slots
                if kernel_name.startswith("packed")
                else slots
                if kernel_name.startswith("pallas")
                else scan_slots
            ),
            **(
                {
                    "chained_windows": (
                        packed_chain
                        if kernel_name.startswith("packed")
                        else chain
                    ),
                    "want_phase": False,
                }
                if kernel_name.startswith(("pallas", "packed"))
                else {}
            ),
            **(
                {"bits_per_vote": 2, "votes_per_word": 16}
                if kernel_name.startswith("packed")
                else {}
            ),
            "kernel": kernel_name,
            "backend": dev0.platform,
        },
        "engine_decisions_per_sec": round(engine_rate, 1),
        "baseline_cpu_engine_per_sec": round(cpu_engine_rate, 1),
        "vs_cpu_engine": round(engine_rate / cpu_engine_rate, 2),
    }
    return 0, out


def _median_iqr(vals: list[float]) -> tuple[float, float, float]:
    """(median, q1, q3) — inclusive quartiles over >= 2 samples."""
    import statistics

    q1, med, q3 = statistics.quantiles(sorted(vals), n=4, method="inclusive")
    return med, q1, q3


def main(argv: list[str] | None = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="Headline consensus benchmark (one JSON line on stdout)."
    )
    ap.add_argument(
        "--repeats",
        type=int,
        default=1,
        metavar="N",
        help="run the full scenario N times and report median ± IQR "
        "instead of a single sample, so round-over-round comparisons "
        "stop riding run-to-run variance",
    )
    ap.add_argument(
        "--sweep",
        nargs="*",
        type=int,
        metavar="CONFIG",
        default=None,
        help="instead of the headline kernel scenario, run the BASELINE "
        "5-config engine sweep (optionally a subset, e.g. --sweep 3 4); "
        "--repeats applies per config, reporting median ± IQR and "
        "settle p50/p99 — one JSON line per config",
    )
    args = ap.parse_args(argv)

    if args.sweep is not None:
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from benchmarks.baseline_sweep import run_sweep

        run_sweep(args.sweep or None, repeats=args.repeats)
        return 0

    if args.repeats <= 1:
        rc, out = _measure_once()
        if rc == 0:
            print(json.dumps(out))
        return rc

    samples: list[dict] = []
    for i in range(args.repeats):
        rc, out = _measure_once()
        if rc != 0:
            return rc
        samples.append(out)
        print(
            f"bench: repeat {i + 1}/{args.repeats}: "
            f"{out['value']:.1f} {out['unit']} ({out['config']['kernel']})",
            file=sys.stderr,
        )

    vals = [s["value"] for s in samples]
    med, q1, q3 = _median_iqr(vals)
    base = sorted(s["baseline_cpu_oracle_per_sec"] for s in samples)[
        len(samples) // 2
    ]
    scan_med, _, _ = _median_iqr([s["scan_decisions_per_sec"] for s in samples])
    agg = dict(samples[-1])  # carry config/env of a real run
    agg["config"] = dict(samples[-1]["config"])  # don't alias the sample's
    agg["value"] = round(med, 1)
    agg["repeats"] = args.repeats
    agg["iqr"] = [round(q1, 1), round(q3, 1)]
    agg["samples"] = [round(v, 1) for v in sorted(vals)]
    agg["baseline_cpu_oracle_per_sec"] = round(base, 1)
    agg["vs_baseline"] = agg["vs_oracle"] = round(med / base, 2)
    agg["scan_decisions_per_sec"] = round(scan_med, 1)
    agg["vs_oracle_scan"] = round(scan_med / base, 2)
    kernels = sorted({s["config"]["kernel"] for s in samples})
    if len(kernels) > 1:
        # repeats adopted different kernels (e.g. a fused run aborted):
        # say so instead of pretending one geometry produced all samples
        agg["config"]["kernel"] = "/".join(kernels)
    eng = [
        s["engine_decisions_per_sec"]
        for s in samples
        if "engine_decisions_per_sec" in s
    ]
    if len(eng) >= 2:
        e_med, e_q1, e_q3 = _median_iqr(eng)
        agg["engine_decisions_per_sec"] = round(e_med, 1)
        agg["engine_iqr"] = [round(e_q1, 1), round(e_q3, 1)]
        e_base = [
            s["baseline_cpu_engine_per_sec"]
            for s in samples
            if "baseline_cpu_engine_per_sec" in s
        ]
        b_med = sorted(e_base)[len(e_base) // 2]
        agg["baseline_cpu_engine_per_sec"] = round(b_med, 1)
        agg["vs_cpu_engine"] = round(e_med / b_med, 2)
    print(json.dumps(agg))
    return 0


def _mesh_engine_rate(S: int, replicas: int) -> float:
    """End-to-end decisions/s of the full device-plane SMR stack in its
    production bulk shape: full-width PayloadBlocks through the block
    lane with the device-resident KV table (consensus + apply fused on
    device, responses derived host-side, block futures settled).
    Delegates to the canonical measurement in
    benchmarks/mesh_engine_bench.py so the methodology lives in one
    place. An incomplete drain or a lane demotion raises there."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from benchmarks.mesh_engine_bench import bench_block_lane

    # W=64 x 12 waves: the geometry chosen for the three-deep pipelined
    # commit; not yet measured on the attached chip
    return float(
        bench_block_lane(
            S, replicas, window=64, waves=12, device_store=True
        )["decisions_per_sec"]
    )


def _cpu_engine_rate_quick(S: int, R: int) -> float:
    """The reference-architecture baseline: scalar-lane CPU engine, at
    the SAME geometry as the device-plane engine measurement."""
    import asyncio

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from benchmarks.baseline_sweep import _cpu_engine_rate

    return asyncio.run(_cpu_engine_rate(S=S, R=R, dur=6.0))


if __name__ == "__main__":
    sys.exit(main())
