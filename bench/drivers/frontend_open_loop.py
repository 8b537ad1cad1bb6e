"""Open-loop point reads through the served table's async front end.

Set-up builds a ``TableServer`` over the configuration's records, warms the
read executors of the buckets this traffic can reach (delta depth 0, no
prototype fold) and starts an ``AsyncFrontend``.  The window offers
requests at the traffic's fixed rate: Poisson arrival times, keys drawn by
a scrambled zipfian over the loaded records plus a share of misses.  Each
request's latency runs from its intended arrival to the moment its future
resolved, so a stall also delays the requests queued behind it.

After the window every answer is compared with the data set's reference;
a request that never resolves or fails counts as missing.
"""
from __future__ import annotations

import time
from functools import partial

import numpy as np

from bench.arrivals import ZipfianGenerator, poisson_arrivals
from bench.harness import Check, Outcome
from bench.mix import affine_perm

_PHASES = ("admission", "linger", "dispatch", "device", "scatter")
_COUNTERS = (
    "batch_keys_served_total",
    "batch_keys_padded_total",
    "batch_executions_total",
    "aot_hits_total",
    "aot_misses_total",
    "frontend_failed_total",
)
# End-to-end metric -> percentile of every request's latency.
LATENCY_METRICS = {"get_p50_ms": 50, "get_p99_ms": 99}


def _counters(snap) -> dict:
    out = {name: float(snap.value(name)) for name in _COUNTERS}
    for phase in _PHASES:
        h = snap.histogram("trace_phase_seconds", {"phase": phase})
        out[f"trace_phase_seconds.{phase}.sum"] = h.sum if h else 0.0
        out[f"trace_phase_seconds.{phase}.count"] = float(h.count) if h else 0.0
    return out


class Driver:
    def __init__(self, cell):
        self.cell = cell
        self.spans: dict = {}

    # -- set-up ---------------------------------------------------------------
    def prepare(self) -> None:
        """The data and the requests, on the host (no device work)."""
        cfg = self.cell.config
        self.records = cfg["records_total"] // cfg["partitions"]
        self.data = self.cell.dataset.KVRecords(self.records, cfg["value_cols"], self.cell.seed)
        self._make_requests()

    def setup(self) -> None:
        import jax

        from repro.core.maintenance import CompactionPolicy
        from repro.core.schema import TableSchema
        from repro.core.table import DistributedHashTable, table_mesh
        from repro.serve_table import AsyncFrontend, MicroBatcher, TableServer

        cell, cfg, tr = self.cell, self.cell.config, self.cell.traffic
        serving = cfg["serving"]
        t0 = time.perf_counter()
        self.prepare()
        keys, values = self.data.table()
        self.spans["data_s"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        table = DistributedHashTable(
            table_mesh(cell.devices),
            ("d",),
            hash_range=self.records,
            schema=TableSchema(cfg["key_dtype"], cfg["value_cols"]),
            max_deltas=serving["max_deltas"],
            tombstone_capacity=serving["tombstone_capacity"],
        )
        self.server = TableServer(
            table,
            keys,
            values,
            policy=CompactionPolicy(
                max_delta_depth=serving["max_delta_depth"], fold_k=serving["fold_k"]
            ),
            batcher=MicroBatcher(table, min_bucket=serving["min_bucket"]),
            write_bucket=serving["write_bucket"],
        )
        del keys, values
        jax.block_until_ready(self.server.current().state)
        self.spans["build_s"] = time.perf_counter() - t0

        # A batch holds at most max(flush_keys, one request) keys: warm the
        # buckets of every total up to that, at depth 0 (reads only).
        most = max(serving["flush_keys"], tr["keys_per_request"])
        buckets = sorted({self.server.batcher.bucket_size(t) for t in range(1, most + 1)})
        t0 = time.perf_counter()
        self.server.warm(buckets=buckets, depths=(0,), fold_horizon=0, profile=False)
        self.spans["warm_s"] = time.perf_counter() - t0

        self.fe = AsyncFrontend(
            self.server,
            linger=serving["linger_s"],
            flush_keys=serving["flush_keys"],
            tracing=serving["tracing"],
        )
        self.fe.start()
        # The first requests through the front end: its threads and the
        # executable's first run, outside the window.
        warm = [self.fe.submit_query(self.keys[i]) for i in range(tr["warmup_requests"])]
        for f in warm:
            f.result(timeout=tr["result_wait_s"])

    def _make_requests(self) -> None:
        """The same work for every seed: ``rate * seconds`` requests, the same
        gaps and the same number of misses, in a seeded order."""
        cell, tr, records = self.cell, self.cell.traffic, self.records
        rng = np.random.default_rng([cell.seed, 1])
        n = int(round(tr["rate_per_s"] * cell.seconds))
        k = tr["keys_per_request"]
        self.arrivals = poisson_arrivals(n, cell.seconds, rng)
        total = n * k
        misses = int(round(tr["miss_share"] * total))
        ranks = ZipfianGenerator(records, tr["zipfian_theta"], rng).sample(total - misses)
        a, b = affine_perm(records, cell.seed, 20)  # scrambles rank -> record
        index = np.empty(total, np.uint64)
        index[: total - misses] = (ranks.astype(np.uint64) * np.uint64(a) + np.uint64(b)) % np.uint64(records)
        index[total - misses :] = records + rng.integers(0, records, misses, dtype=np.uint64)
        flat = self.data.key_of(index[rng.permutation(total)])
        self.keys = [flat[i * k : (i + 1) * k] for i in range(n)]

    # -- window ---------------------------------------------------------------
    def window(self) -> None:
        cell, fe, n = self.cell, self.fe, len(self.keys)
        self.done = np.full(n, np.nan)
        self.submitted = np.full(n, np.nan)
        self.futures = []
        self.before = _counters(self.server.metrics(refresh=False))
        cell.compiles.arm()

        def resolved(i, _fut):
            self.done[i] = time.perf_counter()

        with cell.window_span():
            t0 = time.perf_counter() + 0.001
            self.due = t0 + self.arrivals
            for i in range(n):
                wait = self.due[i] - time.perf_counter()
                if wait > 0:
                    with cell.span("bench.await_arrival"):
                        time.sleep(wait)
                with cell.span("bench.submit"):
                    f = fe.submit_query(self.keys[i])
                self.submitted[i] = time.perf_counter()
                f.add_done_callback(partial(resolved, i))
                self.futures.append(f)
        self.wait_end = time.perf_counter() + cell.traffic["result_wait_s"]
        for f in self.futures:
            try:
                f.exception(timeout=max(0.0, self.wait_end - time.perf_counter()))
            except TimeoutError:
                pass
        self.compiles_in_window = cell.compiles.disarm()

    def collect(self) -> None:
        """The answers of the window's requests (None: none came)."""
        self.answers = []
        self.missing = 0
        for f in self.futures:
            if f.done() and f.exception() is None:
                self.answers.append(np.asarray(f.result().counts, np.int64))
            else:
                self.answers.append(None)
                self.missing += 1

    def release(self) -> None:
        self.fe.stop()  # joins its threads: every trace is finished
        self.fe.metrics()  # refreshes trace_live in the shared registry
        snap = self.server.metrics()
        after = _counters(snap)
        self.counters = {k: after[k] - self.before[k] for k in after}
        self.dropped_rows = int(snap.value("serve_dropped_rows"))
        self.open_traces = int(snap.value("trace_live"))
        self.collect()
        self.futures = self.fe = self.server = None

    # -- comparison -------------------------------------------------------------
    def wrong_answers(self, answers) -> int:
        """Requests whose answer differs from the reference's (None: none came)."""
        want = self.data.count(np.concatenate(self.keys))
        k = self.cell.traffic["keys_per_request"]
        return sum(
            got is not None and not np.array_equal(got, want[i * k : (i + 1) * k])
            for i, got in enumerate(answers)
        )

    def check(self) -> list:
        return [
            Check("wrong_answers", self.wrong_answers(self.answers), 0),
            Check("missing_answers", self.missing, 0),
            Check("aot_misses", int(self.counters["aot_misses_total"]), 0),
            Check("compiles_in_window", self.compiles_in_window, 0),
            Check("dropped_rows", self.dropped_rows, 0),
            Check("open_traces", self.open_traces, 0),
        ]

    def latency(self) -> dict:
        """The end-to-end latencies over every request of the window, and
        what the host saw besides; after :meth:`collect`."""
        # A request with no answer waited at least until the wait ended.
        latency = np.where([a is None for a in self.answers], self.wait_end, self.done) - self.due
        return {
            **{name: float(np.percentile(latency, q)) * 1e3 for name, q in LATENCY_METRICS.items()},
            "requests": len(self.answers),
            "generator_late_p99_ms": float(np.percentile(self.submitted - self.due, 99)) * 1e3,
            "completed_per_s": float(np.isfinite(self.done).sum() / (np.nanmax(self.done) - self.due[0])),
        }

    def outcome(self) -> Outcome:
        seen = self.latency()
        return Outcome(
            end_to_end={name: seen.pop(name) for name in LATENCY_METRICS},
            attempted=len(self.answers),
            failed=self.missing,
            spans=self.spans,
            counters=self.counters,
            work={},
            info=seen,
        )


def control(driver) -> dict:
    """The control: the reference in the program's place, answering by a
    32-bit fingerprint of the key (its low word) without the full-key
    compare that the configuration's exact answers need.  Computed on the
    default device at the cell's size; the comparison must fail it."""
    import jax.numpy as jnp

    data = driver.data
    low = lambda k: jnp.asarray((np.asarray(k, np.uint64) & np.uint64(0xFFFFFFFF)).astype(np.uint32))
    table = jnp.sort(low(data.key_of(np.arange(data.records, dtype=np.uint64))))
    q = low(np.concatenate(driver.keys))
    counts = np.asarray(
        jnp.searchsorted(table, q, side="right") - jnp.searchsorted(table, q, side="left")
    ).astype(np.int64)
    k = driver.cell.traffic["keys_per_request"]
    answers = [counts[i * k : (i + 1) * k] for i in range(len(driver.keys))]
    return {"wrong_answers": driver.wrong_answers(answers)}
