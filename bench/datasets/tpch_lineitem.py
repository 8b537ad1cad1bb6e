"""One chip's share of TPC-H LINEITEM keyed by orderkey, and its reference.

The deployment holds ``orders_total`` orders (TPC-H: 1.5M per scale
factor) partitioned over ``partitions`` chips; this chip holds
``orders_here = orders_total // partitions`` of them, chosen from the seed
by a bijection of the order index.  Order ``o`` has TPC-H's sparse key
``(o // 8) * 32 + o % 8 + 1`` (8 used keys in every 32).  The ``j``-th
order held here has ``1 + j % 7`` lineitems (uniform over 1..7 with a row
count that is the same for every seed).  A lineitem's four int32 columns
are the ones Q3 and Q18 read, drawn from TPC-H's ranges by a seeded hash of
(orderkey, line): quantity 1..50, extendedprice in cents (quantity times a
retail price of 900.00..2099.99), discount 0..1000 basis points in steps
of 100, shipdate in days since 1970 (orderdate + 1..121, orderdate between
1992-01-01 and 1998-08-02).  dbgen's own random stream is not reproduced.

The reference answers a probe key by inverting the bijections: no table,
no sort.
"""
from __future__ import annotations

import numpy as np

from bench.mix import affine_perm, in_chunks, mix64, seed_word

LINES_MAX = 7
COLUMNS = ("quantity", "extendedprice_cents", "discount_bp", "shipdate_days")
_START_DAY = 8035  # 1992-01-01
_LAST_ORDER_DAY = 10440  # 1998-12-31 minus 151 days


def _low32(h: np.ndarray) -> np.ndarray:
    return (h & np.uint64(0xFFFFFFFF)).astype(np.uint32)


def _high32(h: np.ndarray) -> np.ndarray:
    return (h >> np.uint64(32)).astype(np.uint32)


def _scale(bits: np.ndarray, m: int, width: int) -> np.ndarray:
    """Uniform ``bits`` of ``width`` bits onto ``0..m-1`` (multiply and shift,
    no division: ``(bits * m) >> width``)."""
    return (bits * np.uint32(m)) >> np.uint32(width)


class LineitemShare:
    """The lineitems of the orders one chip holds: generator and reference."""

    def __init__(self, orders_total: int, partitions: int, seed: int):
        self.orders_total = int(orders_total)
        self.orders_here = self.orders_total // int(partitions)
        self._a, self._b = affine_perm(self.orders_total, seed, 10)
        self._a_inv = pow(self._a, -1, self.orders_total)
        self._s = seed_word(seed, 11)
        self._s_date = seed_word(seed, 12)
        self._probe = affine_perm(self.orders_here, seed, 13)

    @property
    def rows(self) -> int:
        """Lineitem rows held here (the same for every seed)."""
        full, rest = divmod(self.orders_here, LINES_MAX)
        return full * LINES_MAX * (LINES_MAX + 1) // 2 + rest * (rest + 1) // 2

    # -- keys -------------------------------------------------------------------
    def orderkey(self, j) -> np.ndarray:
        """Orderkey (uint32) of the ``j``-th order held here."""
        o = (np.asarray(j, np.int64) * self._a + self._b) % self.orders_total
        return ((o // 8) * 32 + o % 8 + 1).astype(np.uint32)

    def probe_order(self, t) -> np.ndarray:
        """Which order (``j``) the ``t``-th probe of a shuffled pass reads."""
        a, b = self._probe
        return (np.asarray(t, np.int64) * a + b) % self.orders_here

    def morsel(self, index: int, size: int) -> np.ndarray:
        """Probe keys of morsel ``index``: ``size`` consecutive probes of the
        shuffled pass over the orders held here, wrapping at its end."""
        t = (index * size + np.arange(size, dtype=np.int64)) % self.orders_here
        return self.orderkey(self.probe_order(t))

    # -- rows -------------------------------------------------------------------
    def order_day(self, keys) -> np.ndarray:
        """Orderdate (days since 1970, uint32) of orderkeys ``keys``."""
        h = _high32(mix64(np.asarray(keys, np.uint64) ^ self._s_date)) >> 16
        return _START_DAY + _scale(h, _LAST_ORDER_DAY - _START_DAY + 1, 16)

    def values_of(self, keys, lines, day) -> np.ndarray:
        """``(n, 4)`` int32 columns of lineitem ``lines`` of orders ``keys``,
        whose orderdates are ``day``."""
        word = (np.asarray(keys, np.uint64) << np.uint64(3)) | np.asarray(lines, np.uint64)
        h = mix64(word ^ self._s)
        lo, hi = _low32(h), _high32(h)
        out = np.empty((h.shape[0], 4), np.int32)
        qty = 1 + _scale(lo & 0xFFFF, 50, 16)
        out[:, 0] = qty
        retail = 90000 + _scale(lo >> 16, 20001, 16) + 100 * _scale(hi & 0xFFFF, 1000, 16)
        out[:, 1] = qty * retail
        out[:, 2] = 100 * _scale((hi >> 16) & 0xFF, 11, 8)
        out[:, 3] = day + 1 + _scale(hi >> 24, 121, 8)
        return out

    def table(self) -> tuple[np.ndarray, np.ndarray]:
        """Every lineitem held here: ``(orderkeys uint32 (N,), (N, 4) int32)``.

        Orders come in periods of 7 (1, 2, ..., 7 lineitems: 28 rows), so a
        chunk of whole periods starts at row ``4 * first order``.
        """
        rows_per_period = LINES_MAX * (LINES_MAX + 1) // 2
        period = np.concatenate([np.arange(c) for c in range(1, LINES_MAX + 1)])
        keys = np.empty(self.rows, np.uint32)
        values = np.empty((self.rows, 4), np.int32)

        def fill(p0, p1):  # periods p0..p1 of orders
            j = np.arange(p0 * LINES_MAX, min(p1 * LINES_MAX, self.orders_here))
            lines = 1 + j % LINES_MAX
            order_keys = self.orderkey(j)
            r0 = p0 * rows_per_period
            r1 = r0 + int(lines.sum())
            keys[r0:r1] = np.repeat(order_keys, lines)
            day = np.repeat(self.order_day(order_keys), lines)
            line = np.resize(period.astype(np.uint64), r1 - r0)
            values[r0:r1] = self.values_of(keys[r0:r1], line, day)

        in_chunks(-(-self.orders_here // LINES_MAX), fill, chunk=1 << 17)
        return keys, values

    # -- plain reference ----------------------------------------------------------
    def lines_of(self, keys) -> np.ndarray:
        """How many lineitems each orderkey has here (0 when not held here)."""
        k = np.asarray(keys, np.int64) - 1
        used = (k >= 0) & (k % 32 < 8)
        o = (k // 32) * 8 + k % 32
        j = ((o - self._b) % self.orders_total) * self._a_inv % self.orders_total
        held = used & (j < self.orders_here)
        return np.where(held, 1 + j % LINES_MAX, 0)

    def rows_of(self, keys) -> tuple[np.ndarray, np.ndarray]:
        """Every lineitem of ``keys``: ``(position in keys, (M, 4) values)``."""
        keys = np.asarray(keys, np.uint32)
        lines = self.lines_of(keys)
        pos = np.repeat(np.arange(keys.shape[0]), lines)
        starts = np.repeat(np.cumsum(lines) - lines, lines)
        line = np.arange(pos.shape[0], dtype=np.int64) - starts
        return pos, self.values_of(keys[pos], line, self.order_day(keys)[pos])
