"""Chip smoke: the served hash table, end to end, on TPU at deployment size.

    python chip_smoke.py              # one chip, 2^25 rows
    python chip_smoke.py --chips 4    # four chips, 4 x 2^25 rows, sharded

One process drives the main serving path through its public entry points:
``DistributedHashTable`` -> ``TableServer`` (memcached record shape: u64
keys and 6 int32 value columns, 24 B) -> ``server.warm`` -> about a thousand
``AsyncFrontend.submit_query`` requests of 8-64 keys (hits and misses), a few
``retrieve_many`` batches, upsert and delete chunks that push the delta
stack through a policy fold, then the same reads again.  Every answer is
checked against a plain NumPy reference built from the same seeded data:
counts per key, the multiset of value rows per key, and, after the writes,
last-writer-wins and read-your-writes.  Then the run must be clean: no AOT
misses, no dropped rows, no failed request, no open trace.

The lines before the last describe this one cold run; they are not
benchmark numbers.  The last line is the JSON verdict.  With no TPU, or on
any failed check, the script exits non-zero and prints no verdict.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ROWS_LOG2 = 25  # rows per chip; a fold beside a retrieve leaves no room for 2^26
REQUESTS = 1000  # front-end query requests before the writes
VALUE_COLS = 6  # 24 B values beside 8 B keys: the memcached record shape
WRITE_BUCKET = 512  # rows per delta: every write chunk shares one geometry
MAX_DEPTH = 4  # the policy folds the 2 oldest deltas when 4 are live
FOLD_K = 2
TOMBSTONES = 4096  # every read sorts them: compile and run cost grow with it
READ_BUCKETS = (64, 128, 256)  # query batches of up to 256 keys
RETRIEVE_BUCKET = 64
RETRIEVE_CAPS = (1024, 1024)  # (out, seg) rows per device per batch


def log(msg: str) -> None:
    print(msg, flush=True)


class CheckFailed(Exception):
    pass


class Reference:
    """Plain NumPy model of the table: rows sorted by key, plus the writes.

    Independent of ``repro``: a key's rows are a ``searchsorted`` range of
    the stably sorted key column; an upsert replaces all of a key's rows by
    one (the last written), a delete removes them.
    """

    def __init__(self, keys: np.ndarray, values: np.ndarray):
        self.order = np.argsort(keys, kind="stable")
        self.sorted_keys = keys[self.order]
        self.values = values
        self.written: dict = {}  # key -> latest row, or None once deleted

    def upsert(self, keys, values) -> None:
        for k, v in zip(keys.tolist(), values):
            self.written[k] = v.copy()

    def delete(self, keys) -> None:
        for k in keys.tolist():
            self.written[k] = None

    def counts(self, q: np.ndarray) -> np.ndarray:
        lo = np.searchsorted(self.sorted_keys, q, "left")
        hi = np.searchsorted(self.sorted_keys, q, "right")
        out = (hi - lo).astype(np.int64)
        for i, k in enumerate(q.tolist()):
            if k in self.written:
                out[i] = 0 if self.written[k] is None else 1
        return out

    def rows(self, k: int) -> np.ndarray:
        if k in self.written:
            row = self.written[k]
            return np.zeros((0, VALUE_COLS), np.int32) if row is None else row[None]
        k = np.uint64(k)  # a Python int makes NumPy copy the key column to float64
        lo = np.searchsorted(self.sorted_keys, k, "left")
        hi = np.searchsorted(self.sorted_keys, k, "right")
        return self.values[self.order[lo:hi]]


def _sorted_rows(rows: np.ndarray) -> np.ndarray:
    rows = np.asarray(rows, np.int64).reshape(-1, VALUE_COLS)
    return rows[np.lexsort(rows.T[::-1])] if rows.shape[0] else rows


def make_data(rng, n: int):
    """``n`` rows over ``3n/4`` distinct keys (so keys repeat), random
    values; keys stay below 2^63, misses are drawn above it."""
    universe = rng.integers(1, 1 << 63, size=n * 3 // 4, dtype=np.uint64)
    keys = universe[rng.integers(0, universe.size, size=n)]
    values = rng.integers(0, 1 << 31, size=(n, VALUE_COLS), dtype=np.int32)
    return keys, values


def misses(rng, size: int) -> np.ndarray:
    return rng.integers(1 << 63, (1 << 64) - 1, size=size, dtype=np.uint64)


def query_requests(rng, keys: np.ndarray, count: int, hit_share: float = 0.75):
    out = []
    for size in rng.integers(8, 65, size=count):
        q = keys[rng.integers(0, keys.size, size=size)]
        miss = rng.random(size) >= hit_share
        q[miss] = misses(rng, int(miss.sum()))
        out.append(q)
    return out


def check_queries(fe, ref: Reference, requests, *, min_seqno: int = 0) -> int:
    """Submit every request, then compare each answer; returns mismatches."""
    futures = [fe.submit_query(q) for q in requests]
    bad = 0
    for q, fut in zip(requests, futures):
        res = fut.result(timeout=300)
        if res.seqno < min_seqno:
            raise CheckFailed(f"read served at seqno {res.seqno} < {min_seqno}")
        bad += int(np.sum(np.asarray(res.counts, np.int64) != ref.counts(q)))
    return bad


def check_retrieves(server, ref: Reference, batches) -> int:
    """``retrieve_many`` per batch; compares value-row multisets per key."""
    bad = 0
    for reqs in batches:
        results, _ = server.retrieve_many(reqs)
        for q, per_key in zip(reqs, results):
            for k, got in zip(q.tolist(), per_key):
                want = ref.rows(k)
                if not np.array_equal(_sorted_rows(got), _sorted_rows(want)):
                    bad += 1
    return bad


def retrieve_batches(rng, keys: np.ndarray, count: int):
    """``count`` retrieve batches of 4 requests, at most 64 keys each."""
    return [
        [keys[rng.integers(0, keys.size, size=s)] for s in rng.integers(4, 17, size=4)]
        for _ in range(count)
    ]


def memory_report(devices) -> list:
    stats = [d.memory_stats() for d in devices]
    for d, s in zip(devices, stats):
        if s is None:
            log(f"memory {d}: not reported by this backend")
        else:
            log(
                f"memory {d}: bytes_in_use={s['bytes_in_use']} "
                f"peak_bytes_in_use={s['peak_bytes_in_use']}"
            )
    return stats


def run(devices, *, rows_log2: int, seed: int, requests: int) -> None:
    """The sharded serving path on ``devices`` and its reference checks.

    Raises :class:`CheckFailed` (or the path's own error) on any failure.
    """
    import jax

    from benchmarks.common import assert_clean_run
    from repro.core import maintenance, plans
    from repro.core.maintenance import CompactionPolicy, allocated_rows
    from repro.core.schema import TableSchema
    from repro.core.table import DistributedHashTable, table_mesh
    from repro.serve_table import AsyncFrontend, MicroBatcher, TableServer

    def phase_done(name: str, t0: float) -> None:
        # Logged as each phase ends, so a run cut short still shows its time.
        log(f"phase {name}: {time.perf_counter() - t0:.3f} s (wall, one cold run)")

    chips = len(devices)
    n = chips << rows_log2
    rng = np.random.default_rng(seed)

    t0 = time.perf_counter()
    keys, values = make_data(rng, n)
    ref = Reference(keys, values)
    phase_done("data_and_reference", t0)

    t0 = time.perf_counter()
    table = DistributedHashTable(
        table_mesh(devices),
        ("d",),
        hash_range=n,
        schema=TableSchema("uint64", VALUE_COLS),
        max_deltas=MAX_DEPTH,
        tombstone_capacity=TOMBSTONES,
    )
    server = TableServer(
        table,
        keys,
        values,
        policy=CompactionPolicy(max_delta_depth=MAX_DEPTH, fold_k=FOLD_K),
        batcher=MicroBatcher(table, min_bucket=READ_BUCKETS[0]),
        write_bucket=WRITE_BUCKET,
    )
    state = server.current().state
    jax.block_until_ready(state)
    phase_done("build", t0)
    table_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(state))
    log(
        f"table: {n} rows of u64 keys x {VALUE_COLS} int32 columns over "
        f"{chips} chip(s); {allocated_rows(state)} allocated rows, "
        f"{table_bytes} bytes of state arrays on device"
    )
    stats = memory_report(devices)
    if chips > 1 and all(s is not None for s in stats):
        peaks = [s["peak_bytes_in_use"] for s in stats]
        if max(peaks) > 1.5 * min(peaks):
            raise CheckFailed(f"rows piled unevenly across chips: peaks {peaks}")

    t0 = time.perf_counter()
    warm = server.warm(
        buckets=READ_BUCKETS,
        retrieve_caps={RETRIEVE_BUCKET: RETRIEVE_CAPS},
        fold_horizon=1,
    )
    phase_done("warm", t0)
    baseline_cache = plans.exec_query._cache_size()
    fold_cache = maintenance.exec_fold._cache_size()
    log(
        f"warm: {warm.entries} executables in {warm.compile_seconds:.3f} s "
        f"(buckets {warm.buckets}, depths {warm.depths}, "
        f"fold horizon {warm.fold_horizon})"
    )
    a2a = sorted({(p.kind, p.depth, p.all_to_alls) for p in warm.profiles})
    log(f"all-to-alls per warmed read executor (kind, depth, count): {a2a}")
    if not a2a or any(c != 2 for _, _, c in a2a):
        raise CheckFailed(f"read executors must hold 2 all-to-alls, got {a2a}")
    gather = "pallas kernel" if table.use_kernel else "xla"
    handle = server.batcher.executors.retrieve_handle(
        state, RETRIEVE_BUCKET, *RETRIEVE_CAPS, False
    )
    hlo = handle.compiled.as_text()
    log(
        f"gather: {gather} (warmed retrieve executor holds "
        f"{hlo.count('tpu_custom_call')} Mosaic kernel calls, "
        f"{hlo.count('all-to-all(')} XLA all-to-alls)"
    )

    mismatches = {}
    with AsyncFrontend(server, linger=0.002, flush_keys=READ_BUCKETS[-1]) as fe:
        t0 = time.perf_counter()
        reqs = query_requests(rng, keys, requests)
        mismatches["queries"] = check_queries(fe, ref, reqs)
        phase_done("queries", t0)
        log(f"queries: {len(reqs)} requests, {sum(q.size for q in reqs)} keys")

        t0 = time.perf_counter()
        mismatches["retrieves"] = check_retrieves(
            server, ref, retrieve_batches(rng, keys, 3)
        )
        phase_done("retrieves", t0)

        # Writes: upsert A (existing + new keys), delete, upsert B (re-insert
        # deleted keys, overwrite A's keys, new keys), upsert C.  Five
        # one-bucket delta chunks on a depth-4 policy: exactly one fold, and
        # the tombstones stay under the load that escalates to a compaction.
        t0 = time.perf_counter()
        pick = lambda m: keys[rng.choice(keys.size, size=m, replace=False)]
        fresh = lambda m: rng.integers(1, 1 << 63, size=m, dtype=np.uint64)
        row = lambda m: rng.integers(0, 1 << 31, size=(m, VALUE_COLS), dtype=np.int32)
        up_a = np.concatenate([pick(300), fresh(300)])
        dead = np.unique(pick(128))
        up_b = np.concatenate([dead[:64], up_a[:64], fresh(400)])
        up_c = np.concatenate([up_b[-100:], fresh(200)])
        for kind, k in (("up", up_a), ("del", dead), ("up", up_b), ("up", up_c)):
            if kind == "up":
                v = row(k.size)
                fe.submit_upsert(k, v)
                ref.upsert(k, v)
            else:
                fe.submit_delete(k)
                ref.delete(k)
        server.drain(timeout=600)
        phase_done("writes", t0)
        written = server.stats()
        log(
            f"writes: {written.writes_applied} chunks applied, {written.folds} "
            f"folds ({written.fold_seconds_total:.3f} s host time in the fold "
            f"calls), "
            f"{written.full_compacts} full compactions, delta depth "
            f"{written.shadow.delta_depth}, seqno {written.seqno}"
        )
        if written.folds < 1:
            raise CheckFailed("the writes did not reach a policy fold")
        if maintenance.exec_fold._cache_size() != fold_cache:
            raise CheckFailed("the policy fold compiled after the warm-up")

        t0 = time.perf_counter()
        touched = np.concatenate([up_a, dead, up_b, up_c])
        after = query_requests(rng, keys, requests // 4)
        after += [touched[i : i + 32] for i in range(0, touched.size, 32)]
        mismatches["queries_after_writes"] = check_queries(
            fe, ref, after, min_seqno=written.seqno
        )
        mismatches["retrieves_after_writes"] = check_retrieves(
            server, ref, [[touched[i : i + 16] for i in range(j, j + 64, 16)]
                          for j in range(0, touched.size - 64, 512)]
        )
        phase_done("reads_after_writes", t0)
        fe.metrics()  # refresh the front end's gauges (trace_live) first

    for name, bad in mismatches.items():
        log(f"reference mismatches, {name}: {bad}")
    if any(mismatches.values()):
        raise CheckFailed(f"answers differ from the reference: {mismatches}")
    snap = server.metrics()
    assert_clean_run(snap, baseline_cache_size=baseline_cache, context="chip smoke")
    log(
        f"clean run: aot_misses={int(snap.value('aot_misses_total'))} "
        f"dropped_rows={int(snap.value('serve_dropped_rows'))} "
        f"failed_requests={int(snap.value('frontend_failed_total'))} "
        f"open_traces={int(snap.value('trace_live'))}"
    )
    memory_report(devices)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX reports {devices[0].platform}", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} TPUs, found {len(devices)}",
              file=sys.stderr)
        return 2

    from repro.utils.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    t0 = time.perf_counter()
    try:
        run(devices[: args.chips], rows_log2=ROWS_LOG2, seed=args.seed,
            requests=REQUESTS)
    except (CheckFailed, AssertionError) as e:
        print(f"chip smoke failed: {e}", file=sys.stderr)
        return 1
    log(f"total: {time.perf_counter() - t0:.3f} s")
    dev = devices[0]
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
