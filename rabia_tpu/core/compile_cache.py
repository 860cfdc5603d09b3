"""Placement of JAX's persistent compilation cache.

Every ``ClusterKernel`` / ``MeshPhaseKernel`` / ``DeviceKVTable`` instance
owns its own jits, and a fresh process starts with nothing compiled, so
every entry point that touches the device calls :func:`place_compile_cache`
once, before its first dispatch:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it; no directory
  is set in code.
- unset: a fixed ``.jax_cache/`` at the checkout root (git-ignored). The
  path is part of the cache key, so it is never a temp name, pid or time.

Either way the size/time thresholds are dropped, because this system's
programs are many and short (a window program compiles in seconds, the
kernel helpers in milliseconds) and the defaults would skip most of them.
"""

from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def place_compile_cache() -> str:
    """Point JAX's persistent compile cache at its one placed directory
    and make short programs cacheable. Returns the directory in use."""
    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not placed:
        placed = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", placed)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return placed
