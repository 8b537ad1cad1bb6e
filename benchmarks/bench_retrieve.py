"""Retrieval subsystem benchmark — count vs materialize, duplicates sweep.

Compares the counting query (``query``) with the two-pass retrieval
pipeline (``retrieve``: count → prefix-sum → gather) and the materialized
join (``inner_join``) as the average key multiplicity grows.  The delta
between the query and retrieve columns is the price of actually producing
the values — the functionality gap WarpSpeed (2509.16407) highlights for
GPU hash tables, closed here for the TPU table.
"""
import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--keys", type=int, default=1 << 18)
    ap.add_argument("--max-dup-log2", type=int, default=6)
    args = ap.parse_args()

    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.common import emit, time_fn
    from repro.core.table import DistributedHashTable, table_mesh

    d = len(jax.devices())
    mesh = table_mesh()
    n = args.keys
    rng = np.random.default_rng(1)

    for dup_log2 in range(0, args.max_dup_log2 + 1, 2):
        dup = 1 << dup_log2
        keys = jnp.asarray(rng.integers(0, max(1, n // dup), size=n, dtype=np.uint32))
        table = DistributedHashTable(
            mesh, ("d",), hash_range=n, capacity_slack=2.0
        )
        state = table.build(keys)
        # every key is its own query: expected fanout == avg multiplicity
        out_cap = 8 * ((4 * dup * (n // d) + 64) // 8)

        def run_retrieve(state, q):
            return table.retrieve(state, q, out_capacity=out_cap, seg_capacity=out_cap)

        def run_join(state, q):
            return table.inner_join(state, q, out_capacity=out_cap, seg_capacity=out_cap)

        res = run_retrieve(state, keys)
        assert int(res.num_dropped) == 0, "benchmark capacity sizing bug"
        sec_q = time_fn(table.query, state, keys)
        sec_r = time_fn(run_retrieve, state, keys)
        sec_j = time_fn(run_join, state, keys)
        results = int(np.asarray(res.counts).sum())
        emit(
            "retrieve",
            sec_r,
            avg_occurrence=dup,
            results=results,
            query_keys_per_sec=f"{n / sec_q:.3e}",
            retrieve_keys_per_sec=f"{n / sec_r:.3e}",
            retrieve_results_per_sec=f"{results / sec_r:.3e}",
            join_pairs_per_sec=f"{results / sec_j:.3e}",
        )


if __name__ == "__main__":
    main()
