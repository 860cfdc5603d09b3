"""Transport-engine block lane with the DEVICE kernel: backend="jax"
(fused node_cycle — one dispatch + one fetch per tick) vs the numpy host
kernel at the same width, on whatever backend jax exposes (the record
carries its ``backend``; not yet run on the attached chip).

Engine-level decisions/s at 4096 shards with the device kernel, recorded
into ``results.json`` under ``jax_engine_r03``. Usage::

    python benchmarks/jax_engine_bench.py [--record] [--quick]
"""

from __future__ import annotations

import asyncio
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax
import numpy as np

from benchmarks.baseline_sweep import (  # noqa: E402
    _block_pump,
    _committed,
    _mk_mem_cluster,
    _stop,
)


async def engine_block_rate(S: int, R: int, backend: str, dur: float) -> dict:
    from rabia_tpu.apps import make_sharded_kv
    from rabia_tpu.apps.kvstore import encode_set_bin

    def factory():
        sm, _ = make_sharded_kv(S)
        return sm

    _, hub, engines, _, tasks = await _mk_mem_cluster(
        S, R, factory, backend=backend
    )
    one_op = [[encode_set_bin(f"k{s}", "v")] for s in range(S)]
    # warmup wave; the jax backend needs the fused-dispatch compile (tens
    # of seconds per engine on a cold TPU cache) fully behind it
    warmup = min(3.0, dur / 2) if backend == "host" else max(60.0, dur)
    await _block_pump(engines, S, R, warmup, lambda s: one_op[s])
    base, _ = await _committed(engines)
    t0 = time.perf_counter()
    await _block_pump(engines, S, R, dur, lambda s: one_op[s])
    top, _ = await _committed(engines)
    dt = time.perf_counter() - t0
    await _stop(engines, tasks)
    return {
        "backend": backend,
        "shards": S,
        "replicas": R,
        "decisions_per_sec": round((top - base) / dt, 1),
        "elapsed_s": round(dt, 2),
    }


def main() -> None:
    quick = "--quick" in sys.argv
    dur = 4.0 if quick else 10.0
    S, R = (512, 3) if quick else (4096, 5)
    out = {
        "note": (
            "transport-engine block lane, host vs jax (fused node_cycle) "
            "kernels, same in-memory cluster harness"
        ),
        "platform": jax.devices()[0].platform,
    }
    for backend in ("host", "jax"):
        res = asyncio.run(engine_block_rate(S, R, backend, dur))
        out[backend] = res
        print(backend, "->", res["decisions_per_sec"], "decisions/s")
    out["jax_vs_host"] = round(
        out["jax"]["decisions_per_sec"]
        / max(1e-9, out["host"]["decisions_per_sec"]),
        3,
    )
    print("jax/host ratio:", out["jax_vs_host"])

    if "--record" in sys.argv:
        path = Path(__file__).parent / "results.json"
        doc = json.loads(path.read_text()) if path.exists() else {}
        doc["jax_engine_r03"] = out
        path.write_text(json.dumps(doc, indent=1))
        print("recorded -> results.json jax_engine_r03")


if __name__ == "__main__":
    main()
