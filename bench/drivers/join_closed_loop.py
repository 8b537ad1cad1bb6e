"""Closed-loop bulk probes of a built table through ``plan_join``.

Set-up builds the configuration's table once (``DistributedHashTable.init``),
places the probe morsels on the device, and warms the one join executable.
The window is one stream: it probes a morsel, waits for every output row,
and probes the next, cycling through a seeded shuffled pass over the keys.
It bypasses the front end and the batcher: the executors do all the work.

The comparison: every call's row count against the reference, and every
output row of each call whose morsel the window had not probed before (in
a window shorter than one pass, every call), key by key: the number of rows
and two independent 32-bit hash sums of the rows (a multiset comparison).
"""
from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from bench.harness import Check, Outcome
from bench.mix import hash32
from bench.roofline import probe_min_bytes

RATE_METRIC = "probe_keys_per_s"


def _row_hashes(keys: np.ndarray, values: np.ndarray, salt: int) -> np.ndarray:
    """A 32-bit hash of each (key, value row), as float64 (exact sums)."""
    h = hash32(keys.astype(np.uint64), salt)
    for c in range(values.shape[1]):
        h = hash32(h.astype(np.uint64) << np.uint64(32) | values[:, c].view(np.uint32), salt + 1 + c)
    return h.astype(np.float64)


def _per_key(keys, pos, values, n):
    """Rows, and two hash sums of the rows, per probe position."""
    rows = np.bincount(pos, minlength=n)
    sums = [np.bincount(pos, weights=_row_hashes(keys[pos], values, s), minlength=n)
            for s in (1, 101)]
    return rows, sums


class Driver:
    def __init__(self, cell):
        self.cell = cell
        self.spans: dict = {}

    def prepare(self) -> None:
        """The data set and the probe morsels, on the host (no device work)."""
        cfg = self.cell.config
        self.data = self.cell.dataset.LineitemShare(
            cfg["orders_total"], cfg["partitions"], self.cell.seed
        )
        self.morsel_keys = self.cell.traffic["morsel_keys"]
        self.n_morsels = -(-self.data.orders_here // self.morsel_keys)
        self.host_morsels = [
            self.data.morsel(m, self.morsel_keys) for m in range(self.n_morsels)
        ]

    def setup(self) -> None:
        import jax

        from repro.core.schema import TableSchema
        from repro.core.table import DistributedHashTable, table_mesh

        cell, cfg, tr = self.cell, self.cell.config, self.cell.traffic
        t0 = time.perf_counter()
        self.prepare()
        keys, values = self.data.table()
        self.spans["data_s"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        table = DistributedHashTable(
            table_mesh(cell.devices),
            ("d",),
            hash_range=keys.shape[0],
            schema=TableSchema(cfg["key_dtype"], cfg["value_cols"]),
        )
        self.state = table.init(keys, values)
        del keys, values
        jax.block_until_ready(self.state)
        self.spans["build_s"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        self.plan = table.plan_join(
            num_queries=self.morsel_keys,
            out_capacity=tr["out_capacity"],
            seg_capacity=tr["seg_capacity"],
        )
        self.morsels = [jax.device_put(m, table.key_sharding()) for m in self.host_morsels]
        jax.block_until_ready(self.plan(self.state, self.morsels[0]))
        self.spans["warm_s"] = time.perf_counter() - t0

    def window(self) -> None:
        import jax

        cell = self.cell
        self.checked = []  # (call index, result): the first call of each morsel
        self.totals = []  # (num_results, num_dropped) device arrays per call
        cell.compiles.arm()
        with cell.window_span():
            t0 = time.perf_counter()
            i = 0
            while True:
                with cell.span("bench.call"):
                    res = self.plan(self.state, self.morsels[i % self.n_morsels])
                    jax.block_until_ready(res)
                end = time.perf_counter()
                self.totals.append((res.num_results, res.num_dropped))
                if i < self.n_morsels:
                    self.checked.append((i, res))
                i += 1
                if end - t0 >= cell.seconds:
                    break
            self.window_s = end - t0
        self.compiles_in_window = cell.compiles.disarm()

    def release(self) -> None:
        self.totals = [(np.asarray(n), int(d)) for n, d in self.totals]
        self.checked = [
            (i, np.asarray(r.query_idx), np.asarray(r.values), np.asarray(r.num_results))
            for i, r in self.checked
        ]
        self.state = self.plan = self.morsels = None

    def wrong_keys(self, call: int, query_idx, values, num_results) -> int:
        """Probe keys of call ``call`` whose output rows (a ``ShardJoin``'s
        arrays, on the host) differ from the reference's, as multisets."""
        m = self.morsel_keys
        keys = self.host_morsels[call % self.n_morsels]
        out_cap = query_idx.shape[0] // num_results.shape[0]
        valid = (np.arange(query_idx.shape[0]) % out_cap) < np.repeat(num_results, out_cap)
        qi = query_idx[valid]
        values = values[valid].reshape(-1, values.shape[-1])
        bad = (qi < 0) | (qi >= m)
        got = _per_key(keys, qi[~bad], values[~bad], m)
        want = _per_key(keys, *self.data.rows_of(keys), m)
        differs = got[0] != want[0]
        for g, w in zip(got[1], want[1]):
            differs |= g != w
        return int(differs.sum()) + int(bad.sum())

    def check(self) -> list:
        self.expected_rows = [int(self.data.lines_of(k).sum()) for k in self.host_morsels]
        incomplete = sum(
            int(d != 0 or n.sum() != self.expected_rows[i % self.n_morsels])
            for i, (n, d) in enumerate(self.totals)
        )
        self.complete_calls = len(self.totals) - incomplete
        with ThreadPoolExecutor(max_workers=8) as pool:  # NumPy releases the GIL
            wrong = sum(pool.map(lambda call: self.wrong_keys(*call), self.checked))
        return [
            Check("wrong_keys", wrong, 0),
            Check("incomplete_calls", incomplete, 0),
            Check("compiles_in_window", self.compiles_in_window, 0),
        ]

    def outcome(self) -> Outcome:
        cfg = self.cell.config
        calls = len(self.totals)
        rows = np.mean([self.expected_rows[i % self.n_morsels] for i in range(calls)])
        key_bytes = 4 if cfg["key_dtype"] == "uint32" else 8
        return Outcome(
            end_to_end={RATE_METRIC: self.complete_calls * self.morsel_keys / self.window_s},
            attempted=calls,
            failed=calls - self.complete_calls,
            spans=self.spans,
            counters={},
            work={
                "exec_join": {
                    "min_bytes_per_call": probe_min_bytes(
                        self.morsel_keys,
                        int(round(rows)),
                        key_bytes=key_bytes,
                        value_bytes=4 * cfg["value_cols"],
                    )
                },
            },
        )


def control(driver) -> dict:
    """The control: the reference in the program's place with its payload
    narrowed to int16 (the step that would halve the bytes a probe moves),
    over every morsel of one pass, as many calls as a window checks at
    most; the comparison must fail it."""
    wrong = 0
    for call in range(driver.n_morsels):
        keys = driver.host_morsels[call]
        pos, values = driver.data.rows_of(keys)
        narrow = values.astype(np.int16).astype(np.int32)
        wrong += driver.wrong_keys(call, pos.astype(np.int32), narrow, np.array([pos.shape[0]]))
    return {"wrong_keys": wrong}
