"""Multi-OS-process cluster harness: spawn one Python child per replica.

Shared by the drivers that exercise the true production deployment shape
(one process per replica over the native TCP plane on localhost):
``examples/multiprocess_cluster.py`` and
``benchmarks/multiproc_latency.py``. Reference analog: the reference's
examples run all nodes in-process; process-per-replica is this repo's
stricter variant.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent.parent


def free_ports(n: int) -> list[int]:
    """n distinct ephemeral localhost ports (close-then-rebind pattern —
    a tiny steal window exists; callers treat bind failure as retryable)."""
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


def run_replica_cluster(
    replica_code: str,
    n: int,
    extra_args: list[str],
    *,
    timeout: float = 240.0,
) -> list[str]:
    """Launch ``n`` children running ``replica_code`` (argv: index,
    ports-json, *extra_args), collect each stdout, and NEVER orphan
    survivors: any child failing or hanging kills the rest.

    Returns the per-child stdout. Raises SystemExit on a nonzero child.
    """
    ports = free_ports(n)
    env = dict(os.environ)
    env["PYTHONPATH"] = f"{REPO}{os.pathsep}" + env.get("PYTHONPATH", "")
    # replica children run the host transport engine and never need the
    # chip; assigned (not defaulted) so an environment that names the
    # TPU cannot hand N children a device one process may hold
    env["JAX_PLATFORMS"] = "cpu"
    procs = [
        subprocess.Popen(
            [
                sys.executable, "-c", replica_code,
                str(i), json.dumps(ports), *extra_args,
            ],
            stdout=subprocess.PIPE,
            text=True,
            env=env,
            cwd=REPO,
        )
        for i in range(n)
    ]
    outs: list[str] = []
    try:
        for i, p in enumerate(procs):
            out, _ = p.communicate(timeout=timeout)
            outs.append(out)
            if p.returncode != 0:
                # print EVERY collected replica's output, not just the
                # failer's — cross-replica context (who dropped quorum
                # first) is usually the diagnosis
                for j, o in enumerate(outs):
                    print(f"--- replica {j} output ---")
                    print(o)
                raise SystemExit(f"replica {i} failed rc={p.returncode}")
    finally:
        for p in procs:  # a hung/failed replica must not orphan the rest
            if p.poll() is None:
                p.kill()
    return outs
