"""Async serving — futures API, AOT warmup, deadline batching.

The :class:`AsyncFrontend` puts a request queue in front of the
:class:`TableServer`: callers get a ``Future`` back immediately, a
dispatcher thread flushes the queue when a pow2 bucket's worth of keys
accumulates **or** the oldest request's deadline nears, and a scatter
thread resolves futures while the dispatcher already works on the next
batch.  ``server.warm(...)`` AOT-compiles the whole reachable executor
grid first, so no live request ever traces or compiles.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/serve_async.py
"""
import time

import numpy as np

from repro.core.table import DistributedHashTable, table_mesh
from repro.serve_table import (
    AsyncFrontend,
    CompactionPolicy,
    MicroBatcher,
    TableServer,
)


def main() -> None:
    rng = np.random.default_rng(0)
    mesh = table_mesh()
    n = 1 << 12

    table = DistributedHashTable(
        mesh, ("d",), hash_range=n, max_deltas=4, tombstone_capacity=256
    )
    keys = rng.integers(0, n, size=n, dtype=np.uint32)
    values = np.arange(n, dtype=np.int32)

    # write_bucket fixes every insert delta to one geometry — the property
    # that makes the executor grid finite and therefore AOT-warmable.
    server = TableServer(
        table,
        keys,
        values,
        policy=CompactionPolicy(max_delta_depth=2, fold_k=1),
        batcher=MicroBatcher(table, min_bucket=8),
        write_bucket=8,
    )

    # ---- AOT warmup: compile the grid before the first request -------------
    t0 = time.perf_counter()
    warm = server.warm(buckets=(8, 16, 32), depths=(0, 1, 2), fold_horizon=1)
    print(
        f"warmup: {warm.entries} executables in {time.perf_counter() - t0:.1f}s "
        f"(buckets {warm.buckets}, depths {warm.depths}, "
        f"fold horizon {warm.fold_horizon})"
    )

    # ---- the futures API ----------------------------------------------------
    with AsyncFrontend(server, linger=0.002, flush_keys=32) as fe:
        # submit_query never blocks on execution: each call returns a Future
        # the scatter thread resolves once its batch lands.
        futs = [
            fe.submit_query(rng.choice(keys, size=8).astype(np.uint32))
            for _ in range(64)
        ]
        # urgent request: a tight deadline pulls the flush forward instead of
        # waiting out the linger window.
        urgent = fe.submit_query(keys[:4], deadline=fe.clock() + 0.001)

        res = urgent.result(timeout=5.0)
        print(f"urgent request answered at seqno {res.seqno}: {res.counts.tolist()}")

        # writes flow through a bounded backlog into the writer loop; reads
        # keep resolving against the last published snapshot meanwhile.
        # (16 keys = two write_bucket chunks -> depth 2, one policy fold:
        # exactly the structures warmed above, so coverage stays 100%.)
        fresh = rng.integers(n, 2 * n, size=16, dtype=np.uint32)
        fe.submit_insert(fresh)
        fe.submit_delete(keys[:8])
        server.drain()
        after = fe.submit_query(fresh[:4]).result(timeout=5.0)
        print(f"after insert (seqno {after.seqno}): {after.counts.tolist()}")

        for f in futs:
            f.result(timeout=5.0)
        st = fe.stats()
        print(
            f"front end: {st.completed}/{st.submitted} answered in "
            f"{st.batches_dispatched} batches "
            f"({st.batches_fill} fill-triggered, {st.batches_due} deadline-"
            f"triggered), write backpressure waits {st.write_backpressure_waits}"
        )

    # ---- the whole point: zero live compiles --------------------------------
    w = server.stats().warmup
    print(
        f"AOT coverage {w.coverage:.0%}: {w.aot_hits} reads on warmed "
        f"executables, {w.aot_misses} fell back to the jit path"
    )


if __name__ == "__main__":
    main()
