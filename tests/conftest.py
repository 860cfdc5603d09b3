"""Test configuration: force JAX onto a virtual 8-device CPU platform.

The tests run on the CPU, always: sharding tests run over
``--xla_force_host_platform_device_count=8`` CPU devices (the sanctioned way
to validate Mesh/pjit programs without real chips), and the chip is only
ever used through the chip tool (``python chip_smoke.py``). Must run before
jax initializes, hence the env mutation at import time.
"""

import os

# assigned, not defaulted: an environment that names the TPU must not put
# the test suite (hundreds of processes' worth of per-round dispatches, and
# child processes that would fight over the chip) on the accelerator. The
# jax.config update below makes the same choice hold if something imported
# jax before this file ran.
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import asyncio  # noqa: E402
import inspect  # noqa: E402

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "asyncio: run the (coroutine) test on a fresh event loop"
    )
    config.addinivalue_line(
        "markers",
        "jax_backend: exercises the fenced device-array engine backend "
        "(KernelConfig.backend='jax'; deselect with -m 'not jax_backend')",
    )
    config.addinivalue_line(
        "markers",
        "slow: long-build/long-run gates (the full sanitizer matrix "
        "beyond the tier-1 cells); deselect with -m 'not slow' — the "
        "CI sanitizers job covers them all via scripts/sanitize_gate.py",
    )


@pytest.hookimpl(tryfirst=True)
def pytest_pyfunc_call(pyfuncitem):
    """Minimal asyncio test support (pytest-asyncio isn't in this image):
    coroutine tests run on a fresh event loop per test."""
    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in pyfuncitem._fixtureinfo.argnames
        }
        asyncio.run(fn(**kwargs))
        return True
    return None


@pytest.fixture(scope="session")
def jax_devices():
    import jax

    return jax.devices()
