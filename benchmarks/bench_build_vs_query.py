"""Paper §5.3 — build vs query cost.

The paper's query = build a second HashGraph from the query set + list
intersections (~90% build / ~10% intersect).  We time: build, the
query-side second build, the full count query (sorted + paper-faithful
probe), and the join.
"""
import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--keys", type=int, default=1 << 19)
    args = ap.parse_args()

    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    import jax.numpy as jnp
    import numpy as np

    from benchmarks.common import emit, time_fn
    from repro.core.table import DistributedHashTable, table_mesh

    mesh = table_mesh()
    n = args.keys
    rng = np.random.default_rng(3)
    keys = jnp.asarray(rng.integers(0, n, size=n, dtype=np.uint32))
    queries = jnp.asarray(rng.integers(0, n, size=n, dtype=np.uint32))

    table = DistributedHashTable(mesh, ("d",), hash_range=n)
    sec_build = time_fn(table.build, keys)
    state = table.build(keys)
    sec_query = time_fn(table.query, state, queries)
    sec_join = time_fn(table.join_size, state, queries)

    table_p = DistributedHashTable(
        mesh, ("d",), hash_range=n, paper_faithful_probe=True, max_probe=32
    )
    state_p = table_p.build(keys)
    sec_query_probe = time_fn(table_p.query, state_p, queries)

    emit("build", sec_build, keys=n, keys_per_sec=f"{n/sec_build:.3e}")
    emit("query_sorted", sec_query, keys=n, keys_per_sec=f"{n/sec_query:.3e}",
         query_over_build=f"{sec_query/sec_build:.2f}")
    emit("query_probe_faithful", sec_query_probe, keys=n,
         keys_per_sec=f"{n/sec_query_probe:.3e}")
    emit("join_size", sec_join, keys=n, keys_per_sec=f"{n/sec_join:.3e}")


if __name__ == "__main__":
    main()
