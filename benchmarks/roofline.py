"""Roofline profiling for the fused fault-free window kernel.

Measures achieved HBM bytes/s for the fused-window variants so
"bandwidth-bound" is a measurement, not a docstring.

Methodology: a single dispatch + sync times the host round trip
together with the kernel, which buries a sub-millisecond kernel. Here
each variant is timed as a deep chain of N dispatches over alternating
input buffers with ONE tiny readback at the end (the device queue
executes in order, so forcing the last output forces all N), matching
how the production engine pipelines windows (next-window dispatch
before readback, parallel/mesh_engine.py). Per-dispatch time = chain
time / N, best of 3 chains. A per-T sweep separates the fixed
per-dispatch overhead (the intercept) from the marginal byte rate (the
slope). Chain lengths and depths are not yet re-measured on the
attached chip.

Bytes accounting per decision (T*S decisions): votes R bytes in,
decision 1 byte out, phase 4 bytes out when emitted. The packed rows
(kernel/packed_window.py: 2-bit codes, 16 votes/u32 word) move
(2R+2)/8 bytes per decision — 1.5 at R=5. The HBM peak comes from
``PEAK_HBM_GBPS``, keyed by ``device_kind``; an unknown device is an
error, not a default.

Writes the table into benchmarks/results.json under "roofline_r05"
and prints it. Run on the chip: python benchmarks/roofline.py
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from rabia_tpu.core.types import V1
from rabia_tpu.kernel import fused_window, packed_window

# published HBM peaks by jax ``device_kind`` (Google Cloud documentation,
# "TPU v5e": 16 GB of HBM at 819 GB/s per chip)
PEAK_HBM_GBPS = {
    "TPU v5 lite": 819.0,  # what jax reports for a v5e chip
}


def peak_hbm_gbps() -> float:
    """HBM peak of the device this process runs on; a device that is not
    in the table is an error (a roofline share against another chip's
    peak is not a measurement)."""
    kind = jax.devices()[0].device_kind
    if kind not in PEAK_HBM_GBPS:
        raise RuntimeError(
            f"no published HBM peak for device_kind {kind!r} "
            f"(known: {sorted(PEAK_HBM_GBPS)}); add it with its source "
            "before reporting a roofline share"
        )
    return PEAK_HBM_GBPS[kind]


def _chain_time(fn, inputs, chain: int = 128, reps: int = 3) -> float:
    """Best per-dispatch seconds over `reps` chains of `chain` dispatches.

    `inputs` is a list of distinct input tuples cycled through so no
    caching layer can collapse the chain; the single trailing readback
    forces completion of the whole in-order device queue.
    """
    out = fn(*inputs[0])
    first = out[0] if isinstance(out, tuple) else out
    np.asarray(first[0, :8])  # compile + settle
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for i in range(chain):
            out = fn(*inputs[i % len(inputs)])
        first = out[0] if isinstance(out, tuple) else out
        np.asarray(first[0, :8])
        best = min(best, (time.perf_counter() - t0) / chain)
    return best


def run(T: int = 8192, S: int = 4096, R: int = 5, chain: int = 128) -> dict:
    quorum = R // 2 + 1
    votes = jnp.full((T, S, R), V1, jnp.int8)
    alive = jnp.ones((S, R), bool)
    votes_rm = [
        jnp.full((R, T, S), V1, jnp.int8),
        (jnp.ones((R, T, S), jnp.int8) * jnp.int8(V1)),
    ]
    for v in votes_rm:
        v.block_until_ready()
    alive_rm = jnp.ones((R, S), bool)
    dec_b, ph_b, votes_b = T * S, 4 * T * S, T * S * R

    rows = {}
    peak = peak_hbm_gbps()

    def row(name, secs, bytes_moved):
        rows[name] = {
            "ms_per_dispatch": round(secs * 1e3, 3),
            "decisions_per_sec": round(T * S / secs, 1),
            "GBps": round(bytes_moved / secs / 1e9, 1),
            "pct_peak_hbm": round(100 * bytes_moved / secs / 1e9 / peak, 1),
            "bytes_moved": bytes_moved,
        }

    t = _chain_time(
        lambda v: fused_window.pallas_window_rmajor(v, alive_rm, quorum),
        [(v,) for v in votes_rm],
        chain,
    )
    row("pallas_rmajor", t, votes_b + dec_b + ph_b)

    t = _chain_time(
        lambda v: fused_window.pallas_window_rmajor(
            v, alive_rm, quorum, want_phase=False
        ),
        [(v,) for v in votes_rm],
        chain,
    )
    row("pallas_rmajor_nophase", t, votes_b + dec_b)

    t = _chain_time(
        lambda v: fused_window.closed_form_window_rmajor(v, alive_rm, quorum),
        [(v,) for v in votes_rm],
        chain,
    )
    row("xla_rmajor", t, votes_b + dec_b + ph_b)

    t = _chain_time(
        lambda: fused_window.pallas_window(votes, alive, quorum), [()], chain
    )
    row("pallas_tsr_api", t, votes_b + dec_b + ph_b)

    t = _chain_time(
        lambda: fused_window.closed_form_window(votes, alive, quorum),
        [()],
        chain,
    )
    row("xla_tsr_api", t, votes_b + dec_b + ph_b)

    # nophase variants at the same shape: the apples-to-apples pair for
    # the Pallas-vs-XLA default decision (the production chain runs
    # want_phase=False)
    t = _chain_time(
        lambda v: fused_window.closed_form_window_rmajor(
            v, alive_rm, quorum, want_phase=False
        ),
        [(v,) for v in votes_rm],
        chain,
    )
    row("xla_rmajor_nophase", t, votes_b + dec_b)

    # the packed-vote window at the same T: 16 votes/u32 word, bitwise
    # tally — (2R+2)/8 bytes per decision
    SW = packed_window.packed_width(S)
    packed = [packed_window.pack_codes(v) for v in votes_rm]
    for p in packed:
        p.block_until_ready()
    alive_p = packed_window.pack_alive(alive_rm)
    t = _chain_time(
        lambda p: packed_window.packed_window_rmajor(p, alive_p, quorum),
        [(p,) for p in packed],
        chain,
    )
    row("packed_xla", t, (R + 1) * T * SW * 4)

    return {
        "config": {
            "T": T,
            "S": S,
            "R": R,
            "chain": chain,
            "backend": jax.default_backend(),
            "device_kind": jax.devices()[0].device_kind,
        },
        "methodology": "chained dispatch (pipelined windows), one readback",
        "peak_hbm_GBps": peak,
        "rows": rows,
    }


def t_sweep(S: int = 4096, R: int = 5) -> dict:
    """Per-dispatch time vs window depth T: the intercept is the fixed
    per-dispatch overhead, the slope is the marginal byte rate."""
    quorum = R // 2 + 1
    alive_rm = jnp.ones((R, S), bool)
    out = {}
    prev = None
    for T in (1024, 4096, 16384, 65536):
        votes_rm = [
            jnp.full((R, T, S), V1, jnp.int8),
            (jnp.ones((R, T, S), jnp.int8) * jnp.int8(V1)),
        ]
        for v in votes_rm:
            v.block_until_ready()
        t = _chain_time(
            lambda v: fused_window.pallas_window_rmajor(v, alive_rm, quorum),
            [(v,) for v in votes_rm],
            chain=96,
        )
        entry = {
            "ms_per_dispatch": round(t * 1e3, 3),
            "decisions_per_sec": round(T * S / t, 1),
            "GBps": round((R + 5) * T * S / t / 1e9, 1),
        }
        if prev is not None:
            dT = T - prev[0]
            dt = t - prev[1]
            if dt > 0:
                entry["marginal_GBps"] = round(
                    (R + 5) * dT * S / dt / 1e9, 1
                )
        prev = (T, t)
        out[f"T{T}"] = entry
    return out


def packed_t_sweep(S: int = 4096, R: int = 5) -> dict:
    """Depth sweep for the packed window. Packed buffers are 4x
    smaller, so windows go 4x deeper in the same HBM — the fixed
    per-dispatch overhead is spread over 4x the decisions and the TOTAL
    rate (not just the marginal slope) moves toward the peak."""
    quorum = R // 2 + 1
    peak = peak_hbm_gbps()
    SW = packed_window.packed_width(S)
    alive_p = packed_window.pack_alive(jnp.ones((R, S), bool))
    # one full u32 word of V1 codes — windows are built directly at the
    # packed width (a monolithic i8 plane at T=262144 would not fit)
    word = packed_window.pack_codes(
        jnp.full((packed_window.LANES,), V1, jnp.int8)
    )[0]
    out = {}
    prev = None
    for T in (16384, 65536, 131072, 262144):
        packed = [
            jnp.full((R, T, SW), word, jnp.uint32),
            jnp.full((R, T, SW), word, jnp.uint32),
        ]
        for p in packed:
            p.block_until_ready()
        t = _chain_time(
            lambda p: packed_window.packed_window_rmajor(p, alive_p, quorum),
            [(p,) for p in packed],
            chain=48,
        )
        bm = (R + 1) * T * SW * 4
        entry = {
            "ms_per_dispatch": round(t * 1e3, 3),
            "decisions_per_sec": round(T * S / t, 1),
            "GBps": round(bm / t / 1e9, 1),
            "pct_peak_hbm": round(100 * bm / t / 1e9 / peak, 1),
        }
        if prev is not None:
            dT, dt = T - prev[0], t - prev[1]
            if dt > 0:
                mg = (R + 1) * dT * SW * 4 / dt / 1e9
                entry["marginal_GBps"] = round(mg, 1)
                entry["marginal_pct_peak"] = round(100 * mg / peak, 1)
        prev = (T, t)
        out[f"T{T}"] = entry
        del packed
    return out


def main() -> None:
    from rabia_tpu.core.compile_cache import place_compile_cache

    place_compile_cache()
    out = run(
        T=int(os.environ.get("ROOFLINE_T", 8192)),
        S=int(os.environ.get("ROOFLINE_S", 4096)),
        R=int(os.environ.get("ROOFLINE_R", 5)),
    )
    out["t_sweep"] = t_sweep(
        S=int(os.environ.get("ROOFLINE_S", 4096)),
        R=int(os.environ.get("ROOFLINE_R", 5)),
    )
    out["packed_t_sweep"] = packed_t_sweep(
        S=int(os.environ.get("ROOFLINE_S", 4096)),
        R=int(os.environ.get("ROOFLINE_R", 5)),
    )
    print(json.dumps(out, indent=1))
    path = os.path.join(os.path.dirname(__file__), "results.json")
    try:
        with open(path) as f:
            results = json.load(f)
    except (OSError, json.JSONDecodeError):
        results = {}
    results["roofline_r05"] = out
    with open(path, "w") as f:
        json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
