"""Request streams: zipfian ranks and open-loop arrival times.

:class:`ZipfianGenerator` is a copy of ``repro.cache.workload``'s (YCSB's
bounded zipfian by CDF inversion), kept here so that a change to the
program cannot change the yardstick.  Arrival gaps are the exponential
distribution's quantiles in a seeded order: every seed offers the same
multiset of gaps, so the same work in the same time, and only the order
changes (a Poisson process has independent gaps; these are exchangeable,
which is what the latency tail sees).
"""
from __future__ import annotations

import numpy as np


class ZipfianGenerator:
    """Bounded zipfian ranks: ``P(rank=i)`` is proportional to ``1/(i+1)^theta``.

    Rank 0 is the hottest.  ``theta=0.99`` is YCSB's default skew.
    """

    def __init__(self, n: int, theta: float, rng: np.random.Generator):
        if n < 1:
            raise ValueError("need at least one item")
        self.n = int(n)
        w = np.arange(1, self.n + 1, dtype=np.float64) ** -float(theta)
        self._cdf = np.cumsum(w)
        self._cdf /= self._cdf[-1]
        self.rng = rng

    def sample(self, size: int) -> np.ndarray:
        """``size`` ranks in ``[0, n)``."""
        ranks = np.searchsorted(self._cdf, self.rng.random(size), side="left")
        return np.minimum(ranks, self.n - 1).astype(np.int64)


def poisson_arrivals(count: int, seconds: float, rng: np.random.Generator) -> np.ndarray:
    """``count`` arrival offsets in ``(0, seconds]`` with exponential gaps.

    The gaps are the exponential quantiles at ``(i + 0.5) / count``, scaled
    to sum to ``seconds`` and shuffled by ``rng``; the last arrival is at
    ``seconds``.
    """
    if count < 1:
        raise ValueError("need at least one arrival")
    gaps = -np.log1p(-(np.arange(count) + 0.5) / count)
    gaps *= seconds / gaps.sum()
    return np.cumsum(rng.permutation(gaps))
