"""Dispatch-overhead measurement (SURVEY.md §5.1 / §7.4.4).

Quantifies the per-step cost the engine design amortizes: the same
node-step math evaluated (a) as the numpy host kernel, (b) as a jitted
XLA call on the current backend, at several shard widths. The difference
between (b) at S=1 and (b) at large S is the dispatch overhead one engine
round pays regardless of work; the host-kernel line is why the engine's
CPU round loop runs numpy.

Prints one JSON line per (impl, S).
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np


def host_step_cost(S: int, R: int = 5, reps: int = 200) -> float:
    from rabia_tpu.core.types import ABSENT, V1
    from rabia_tpu.kernel.host_driver import HostNodeKernel

    k = HostNodeKernel(S, R, 0, seed=0)
    st = k.init_state()
    st = k.start_slots(
        st, np.ones(S, bool), np.zeros(S, np.int32), np.full(S, V1, np.int8)
    )
    in1 = np.full((S, R), V1, np.int8)
    in2 = np.full((S, R), ABSENT, np.int8)
    k.node_step(st, in1, in2, None)
    t0 = time.perf_counter()
    for _ in range(reps):
        k.node_step(st, in1, in2, None)
    return (time.perf_counter() - t0) / reps


def jax_step_cost(S: int, R: int = 5, reps: int = 50) -> dict:
    """Three numbers per width (``node_step`` donates its state, so the
    chain threads the returned state):

    - ``enqueue_us``: back-to-back async dispatch (block once at the end)
      — the pipelined throughput ceiling;
    - ``roundtrip_us``: dispatch + device_get per step — what a host loop
      that needs each step's result before the next pays;
    - ``lag1_fetch_us``: dispatch step N, fetch step N-1 — whether a
      one-tick-deep pipeline hides the readback latency (it cannot
      when the readback round trip itself is the floor).
    """
    import jax
    import jax.numpy as jnp

    from rabia_tpu.core.types import ABSENT, V1
    from rabia_tpu.kernel.phase_driver import NodeKernel

    k = NodeKernel(S, R, 0, seed=0)
    in1 = jnp.full((S, R), V1, jnp.int8)
    in2 = jnp.full((S, R), ABSENT, jnp.int8)
    dec = jnp.full((S,), ABSENT, jnp.int8)

    st, ob = k.node_step(k.init_state(), in1, in2, dec)
    jax.block_until_ready(ob.cast_r2)
    t0 = time.perf_counter()
    for _ in range(reps):
        st, ob = k.node_step(st, in1, in2, dec)
    jax.block_until_ready(ob.cast_r2)
    enqueue = (time.perf_counter() - t0) / reps

    t0 = time.perf_counter()
    for _ in range(reps):
        st, ob = k.node_step(st, in1, in2, dec)
        _ = jax.device_get(ob.cast_r2)
    roundtrip = (time.perf_counter() - t0) / reps

    prev = None
    t0 = time.perf_counter()
    for _ in range(reps):
        st, ob = k.node_step(st, in1, in2, dec)
        if prev is not None:
            _ = jax.device_get(prev)
        prev = ob.cast_r2
    lag1 = (time.perf_counter() - t0) / reps

    return {
        "enqueue_us": round(enqueue * 1e6, 1),
        "roundtrip_us": round(roundtrip * 1e6, 1),
        "lag1_fetch_us": round(lag1 * 1e6, 1),
    }


def main() -> int:
    import os

    import jax

    # RABIA_BENCH_BACKEND picks the platform before first device use
    want = os.environ.get("RABIA_BENCH_BACKEND")
    if want:
        jax.config.update("jax_platforms", want)
    backend = jax.default_backend()
    for S in (1, 256, 4096, 16384):
        host = host_step_cost(S)
        dev = jax_step_cost(S)
        print(
            json.dumps(
                {
                    "metric": "node_step_cost_us",
                    "shards": S,
                    "host_numpy_us": round(host * 1e6, 1),
                    "jax_backend": backend,
                    "host_per_shard_ns": round(host / S * 1e9, 1),
                    **dev,
                }
            )
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
