"""Serving the distributed table — mixed insert/delete/query traffic.

A :class:`TableServer` drives the full serving loop: ragged read requests
coalesce onto cached static shapes through the micro-batcher, a writer
loop applies queued mutations to a shadow state and publishes immutable
seqno-stamped snapshots, and compaction runs as an incremental background
fold that never touches the read path.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/serve_table.py
"""
import numpy as np

from repro.core.table import DistributedHashTable, table_mesh
from repro.serve_table import CompactionPolicy, TableServer


def main() -> None:
    rng = np.random.default_rng(0)
    mesh = table_mesh()
    n = 1 << 12

    table = DistributedHashTable(mesh, ("d",), hash_range=n, max_deltas=6)
    keys = rng.integers(0, n, size=n, dtype=np.uint32)
    values = np.arange(n, dtype=np.int32)

    # seqno-0 snapshot; the policy folds the 2 oldest deltas whenever the
    # ring fills, so the write stream below never hits a ring-full error.
    server = TableServer(
        table, keys, values, policy=CompactionPolicy(max_delta_depth=6, fold_k=2)
    )

    # ---- reads: ragged requests, one fused execution ----------------------
    requests = [keys[:5], keys[100:103], keys[200:264]]
    counts, seqno = server.query_many(requests)
    print(f"seqno {seqno}: request sizes {[len(r) for r in requests]} "
          f"-> first counts {counts[0].tolist()}")

    # ---- mixed write traffic, applied by the writer loop ------------------
    for wave in range(12):
        fresh = rng.integers(n, 2 * n, size=64, dtype=np.uint32)
        server.submit_insert(fresh, np.arange(64, dtype=np.int32) + 1000 * wave)
        if wave % 3 == 2:
            server.submit_delete(keys[wave * 16 : wave * 16 + 16])
    server.drain()  # apply + publish everything queued
    stats = server.stats()
    print(f"after traffic: seqno {stats.seqno}, delta depth "
          f"{stats.shadow.delta_depth}, folds {stats.folds}, "
          f"full compacts {stats.full_compacts}")

    # ---- a background fold while reads keep flowing -----------------------
    pre = server.current().seqno
    thread = server.fold_async(k=2) if stats.shadow.delta_depth > 2 else None
    reads = 0
    while thread is not None and thread.is_alive():
        _, seq = server.query_many([keys[:32]])
        assert seq == pre  # the old snapshot serves until the fold publishes
        reads += 1
    if thread is not None:
        thread.join()
    print(f"background fold: {reads} reads served mid-fold at seqno {pre}, "
          f"now at seqno {server.current().seqno}")

    # ---- provenance read: which layer answered? ---------------------------
    (result,), _ = server.retrieve_many([keys[:4]], per_layer_counts=True)
    values4, layer_counts = result
    print(f"per-key values {[v.tolist() for v in values4]} with per-layer "
          f"breakdown\n{layer_counts}")

    # ---- server metrics ----------------------------------------------------
    final = server.stats()
    b = final.batcher
    print(f"served {final.reads} requests in {b.batches} fused batches, "
          f"plan-cache hit rate {b.cache_hits}/{b.cache_hits + b.cache_misses}, "
          f"pad fraction {b.pad_fraction:.2f}, "
          f"skew fallbacks {final.skew_fallbacks}")


if __name__ == "__main__":
    main()
