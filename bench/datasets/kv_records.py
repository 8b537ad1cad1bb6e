"""Key-value records of a memcached-shaped cache, and their plain reference.

Record ``i`` (``0 <= i < records``) has the 64-bit key ``mix64(i ^ s)``,
where ``s`` is drawn from the seed, and ``value_cols`` int32 columns of
seeded hash bits of that key (``value_cols`` even: each 64-bit word of hash
bits fills two columns).  Indices at or above ``records`` give keys
that were never loaded: the misses.  Since ``mix64`` is a bijection, the
reference answers any key by inverting it; it never holds the table.
"""
from __future__ import annotations

import numpy as np

from bench.mix import in_chunks, mix64, seed_word, unmix64

_ALL_ONES = np.uint64((1 << 64) - 1)  # both lanes EMPTY: the table's padding key
# Odd multipliers that spread one word of hash bits into the next ones.
_ODD = [np.uint64(m) for m in (0x9E3779B97F4A7C15, 0xC2B2AE3D27D4EB4F,
                               0xD6E8FEB86659FD93, 0xA0761D6478BD642F)]


class KVRecords:
    """The loaded records of one seed: generator and reference in one."""

    def __init__(self, records: int, value_cols: int, seed: int):
        self.records = int(records)
        self.value_cols = int(value_cols)
        if self.value_cols % 2:
            raise ValueError("value_cols must be even")
        self._s = seed_word(seed, 1)
        self._salt = seed_word(seed, 2)

    def key_of(self, index) -> np.ndarray:
        """The key of record ``index`` (uint64; a miss when ``>= records``)."""
        return mix64(np.asarray(index, np.uint64) ^ self._s)

    def index_of(self, keys) -> np.ndarray:
        """Inverse of :meth:`key_of`: the record index a key was made from."""
        return unmix64(np.asarray(keys, np.uint64)) ^ self._s

    def values_of(self, keys) -> np.ndarray:
        """The ``(n, value_cols)`` int32 value rows stored under ``keys``."""
        word = mix64(np.asarray(keys, np.uint64) ^ self._salt)
        words = [word]
        for odd in _ODD[: self.value_cols // 2 - 1]:
            words.append((word ^ (word >> np.uint64(29))) * odd)
        return np.stack(words, axis=1).view(np.int32)

    def table(self) -> tuple[np.ndarray, np.ndarray]:
        """Every loaded record: ``(keys uint64 (n,), values int32 (n, C))``."""
        keys = np.empty(self.records, np.uint64)
        values = np.empty((self.records, self.value_cols), np.int32)

        def fill(a, b):
            keys[a:b] = self.key_of(np.arange(a, b, dtype=np.uint64))
            values[a:b] = self.values_of(keys[a:b])

        in_chunks(self.records, fill)
        if np.any(keys == _ALL_ONES):
            raise ValueError("a record key equals the table's padding key")
        return keys, values

    # -- plain reference -----------------------------------------------------
    def count(self, keys) -> np.ndarray:
        """How many loaded records hold each key: 1 or 0 (keys are unique)."""
        return (self.index_of(keys) < np.uint64(self.records)).astype(np.int64)
