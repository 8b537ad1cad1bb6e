"""Shared kernel helpers: interpret-mode selection, padding, block defaults."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.utils import cdiv

# Per-kernel ``block_rows`` defaults — ONE table instead of literals
# scattered through ``ops.py`` (murmur used to hard-code 64, every probe/
# gather kernel 8).  These are the fallbacks; the autotuner
# (``repro.kernels.autotune``) overrides them per (kernel, backend, width,
# size bucket) with measured winners.
DEFAULT_BLOCK_ROWS = {
    "murmur": 64,
    "bin_histogram": 8,
    "bucket_probe": 8,
    "csr_gather": 8,
    "csr_gather_batched": 8,
}


def resolve_block_rows(
    kernel: str,
    override: Optional[int] = None,
    *,
    n: Optional[int] = None,
    width: int = 1,
) -> int:
    """The ``block_rows`` an ops-layer wrapper should use for one call.

    Resolution order: explicit ``override`` → autotuned winner (in-process
    cache, seeded from the JSON artifact) → :data:`DEFAULT_BLOCK_ROWS`.
    ``n`` is the kernel's dominant size (queries, capacity, rows) and
    ``width`` its column/lane count — together they pick the autotune
    cache bucket.  This runs *outside* every jit boundary (the public
    wrappers resolve before calling their jitted inner function), so a
    freshly loaded or updated autotune cache takes effect on the next
    call instead of being baked stale into a jit cache entry.
    """
    if override is not None:
        return int(override)
    from repro.kernels import autotune  # local import — autotune times ops

    tuned = autotune.cached_block_rows(kernel, n=n, width=width)
    if tuned is not None:
        return int(tuned)
    return DEFAULT_BLOCK_ROWS[kernel]


def use_interpret_mode() -> bool:
    """Pallas TPU kernels run in interpret mode on non-TPU backends only.

    The tests run on the CPU, where interpret mode checks a kernel's
    results; on a TPU this returns False and the kernels lower through
    Mosaic.  Interpret mode never runs on the chip, and it cannot show
    what Mosaic refuses: ``tests/test_compile_v5e.py`` compiles for a
    described v5e for that.
    """
    return jax.default_backend() != "tpu"


def pad_to_block_1d(x: jax.Array, block: int, fill) -> tuple[jax.Array, int]:
    """Pad a 1-D array up to a multiple of ``block``; returns (padded, n_orig)."""
    n = x.shape[0]
    padded = cdiv(n, block) * block
    if padded != n:
        x = jnp.pad(x, (0, padded - n), constant_values=fill)
    return x, n


def as_lanes(x: jax.Array, lanes: int = 128) -> jax.Array:
    """Reshape a block-padded 1-D array to (rows, lanes) — TPU VPU layout."""
    return x.reshape(-1, lanes)
