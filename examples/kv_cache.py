"""KV-cache mode — upsert, TTL expiry, and capacity-reclaiming eviction.

The cache facade turns the multiset table into a map with lifetimes:
``put`` is insert-or-replace (last writer wins, read-your-writes), TTLs
expire rows against a logical clock the moment it passes their deadline,
and the compaction policy folds expired/superseded rows out of the base
so a steady write stream holds capacity flat.  A YCSB-style zipfian
workload drives the same machinery at the end.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/kv_cache.py
"""
import numpy as np

from repro.cache import KVCache, WORKLOADS, YCSBWorkload
from repro.core.table import DistributedHashTable, table_mesh


def main() -> None:
    mesh = table_mesh()
    table = DistributedHashTable(
        mesh, ("d",), hash_range=1 << 12, max_deltas=4, tombstone_capacity=512
    )

    # ---- put / get / delete: map semantics over the multiset core ----------
    cache = KVCache(table, default_ttl=None)
    keys = np.arange(100, 164, dtype=np.uint32)
    cache.put(keys, np.arange(64, dtype=np.int32))
    cache.put(keys[:8], np.full(8, 777, np.int32))  # overwrite: one live row
    print(f"get after overwrite: {cache.get(keys[:4]).tolist()} "
          f"(live rows: {cache.live_count()})")
    cache.delete(keys[:4])
    print(f"after delete: contains {cache.contains(keys[:8]).tolist()}")

    # ---- TTL: rows age out when the clock passes their deadline ------------
    cache.put(keys[32:40], np.arange(8, dtype=np.int32), ttl=3)
    print(f"t={cache.now}: ttl rows visible = {cache.contains(keys[32:40]).all()}")
    cache.advance(3)
    print(f"t={cache.now}: ttl rows visible = {cache.contains(keys[32:40]).any()} "
          f"(live rows: {cache.live_count()})")

    # ---- eviction: expired capacity is reclaimed, not leaked ---------------
    hot = np.arange(5000, 5064, dtype=np.uint32)
    allocs = []
    for t in range(8):
        cache.put(hot, np.full(64, t, np.int32), ttl=2)  # replace + re-arm
        cache.tick()
        s = cache.stats()
        allocs.append(s.base_rows + s.delta_rows)
    print(f"steady upsert+expire: allocated rows per cycle {allocs}")
    print(f"maintenance: {cache.folds} folds, {cache.evictions} evictions "
          f"(expired tombstones now: {cache.stats().tombstone_expired})")
    reclaimed = cache.evict_expired()
    print(f"forced eviction reclaimed {reclaimed} rows; "
          f"live count {cache.live_count()}")

    # ---- a YCSB-B read-heavy zipfian burst through the cache ---------------
    w = YCSBWorkload(WORKLOADS["B"], 1 << 10, theta=0.99, batch=128, seed=1)
    cache2 = KVCache(table, w.load_keys(), w.load_values())
    reads = writes = 0
    for kind, kk, vv in w.batches(1024):
        if kind == "read":
            reads += kk.shape[0]
            cache2.get(kk)
        else:
            writes += kk.shape[0]
            cache2.put(kk, vv)
    print(f"YCSB-B: {reads} reads / {writes} upserts, "
          f"live {cache2.live_count()}, folds {cache2.folds}")


if __name__ == "__main__":
    main()
