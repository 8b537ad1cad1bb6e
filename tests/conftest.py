"""Shared test configuration.

Forces an 8-way fake host-device platform *before jax initializes* so
multi-device mesh tests run in-process on CPU-only CI.  Subprocess-based
tests (``tests/multidevice``) set their own ``XLA_FLAGS`` and are
unaffected.  If the user already forced a device count, respect it.
"""
import os

_FORCE = "--xla_force_host_platform_device_count"
if _FORCE not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + f" {_FORCE}=8"
    ).strip()

import jax  # noqa: E402  (import after the flag so it takes effect)

import pytest

from repro.core.table import table_mesh

# The suite compiles hundreds of XLA programs in one process; on some
# CPU-only hosts the accumulated compiler/runtime state eventually
# crashes the process (segfault in backend_compile ~240 tests in,
# reproducible on the untouched seed).  Dropping every jit dispatch
# cache at module boundaries releases the executables (and their LLVM
# JIT memory) a finished module pinned, which keeps the single-process
# tier-1 run inside what the toolchain tolerates.  No test observes the
# difference: jit cache-size assertions are all intra-test, and the
# next module simply recompiles what it needs.  Tests never turn on the
# persistent compilation cache (``repro.utils.compile_cache`` is for the
# chip entry points only).


@pytest.fixture(autouse=True, scope="module")
def _bounded_compiler_state():
    yield
    jax.clear_caches()


@pytest.fixture(scope="session")
def mesh8():
    """An 8-way 1-D mesh over the forced host devices."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 (fake) devices; XLA_FLAGS was overridden")
    return table_mesh(jax.devices()[:8])


@pytest.fixture(scope="session")
def mesh1():
    """Single-device mesh (degenerate distributed case)."""
    return table_mesh(jax.devices()[:1])
