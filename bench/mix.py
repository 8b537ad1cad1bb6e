"""Seeded bijections and hashes for making benchmark data from ``--seed``.

Every data set of the benchmark is a function of a record index and the
seed, built from the bijections here, so a plain reference can recompute
any answer from a key alone (by inverting the bijection) instead of
holding and sorting the table.  NumPy only; uint64 and uint32 arithmetic
wraps, which is what these mixes rely on.
"""
from __future__ import annotations

import numpy as np

_M1 = 0xBF58476D1CE4E5B9
_M2 = 0x94D049BB133111EB
_M1_INV = pow(_M1, -1, 1 << 64)
_M2_INV = pow(_M2, -1, 1 << 64)
_MASK64 = (1 << 64) - 1


def _u64(x) -> np.ndarray:
    return np.asarray(x, dtype=np.uint64)


def mix64(x) -> np.ndarray:
    """SplitMix64's finalizer: a bijection of the 64-bit integers."""
    z = _u64(x).copy()
    z ^= z >> np.uint64(30)
    z *= np.uint64(_M1)
    z ^= z >> np.uint64(27)
    z *= np.uint64(_M2)
    z ^= z >> np.uint64(31)
    return z


def unmix64(z) -> np.ndarray:
    """The inverse of :func:`mix64`."""
    x = _u64(z).copy()
    x ^= (x >> np.uint64(31)) ^ (x >> np.uint64(62))
    x *= np.uint64(_M2_INV)
    x ^= (x >> np.uint64(27)) ^ (x >> np.uint64(54))
    x *= np.uint64(_M1_INV)
    x ^= (x >> np.uint64(30)) ^ (x >> np.uint64(60))
    return x


def seed_word(seed: int, salt: int) -> np.uint64:
    """A 64-bit constant drawn from ``seed`` (any Python int) and ``salt``."""
    word = (int(seed) * 0x9E3779B97F4A7C15 + int(salt)) & _MASK64
    return np.uint64(mix64(np.array([word], np.uint64))[0])


def hash32(x, salt: int) -> np.ndarray:
    """32 well-mixed bits of each ``x`` (integers below 2^64), as uint32."""
    z = mix64(_u64(x) ^ np.uint64(mix64(np.array([salt], np.uint64))[0]))
    return (z >> np.uint64(32)).astype(np.uint32)


def affine_perm(n: int, seed: int, salt: int) -> tuple[int, int]:
    """``(a, b)`` such that ``i -> (a*i + b) % n`` permutes ``range(n)``."""
    w = int(seed_word(seed, salt))
    a = (w >> 20) % n | 1
    while np.gcd(a, n) != 1:
        a += 2
    return a, int(seed_word(seed, salt + 1)) % n


def in_chunks(n: int, fill, chunk: int = 1 << 22, workers: int = 8) -> None:
    """Call ``fill(start, stop)`` over ``range(n)`` in chunks on a few threads.

    NumPy releases the interpreter lock inside its large array operations,
    so generating a table chunk by chunk on threads divides the set-up time
    of the data by about the number of cores.  ``fill`` writes its own
    slice of preallocated outputs.
    """
    from concurrent.futures import ThreadPoolExecutor

    bounds = [(a, min(a + chunk, n)) for a in range(0, n, chunk)]
    with ThreadPoolExecutor(max_workers=workers) as pool:
        for f in [pool.submit(fill, a, b) for a, b in bounds]:
            f.result()
