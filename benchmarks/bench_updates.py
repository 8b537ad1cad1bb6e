"""Mutation benchmark — insert/delete/compact throughput vs delta depth.

Measures the versioned plan/execute API (PR 3): functional ``insert``
(delta-graph build), ``delete`` (tombstone append), planned ``retrieve``
execution as the delta ring deepens (each extra delta adds one routed
round per query batch), and ``compact`` (fold deltas + tombstones into a
fresh base).  The query-latency-vs-depth column is the read amplification
an LSM-style design pays before compaction.
"""
import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--keys", type=int, default=1 << 17)
    ap.add_argument("--insert-batch", type=int, default=1 << 12)
    ap.add_argument("--max-depth", type=int, default=8)
    args = ap.parse_args()

    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    import jax.numpy as jnp
    import numpy as np

    from benchmarks.common import emit, time_fn
    from repro.core.table import DistributedHashTable, table_mesh

    mesh = table_mesh()
    n, batch = args.keys, args.insert_batch
    rng = np.random.default_rng(2)

    table = DistributedHashTable(
        mesh, ("d",), hash_range=n, capacity_slack=2.0, max_deltas=args.max_depth
    )
    keys = jnp.asarray(rng.integers(0, n, size=n, dtype=np.uint32))
    state = table.init(keys)
    queries = jnp.asarray(rng.integers(0, n, size=n // 4, dtype=np.uint32))

    sec_build = time_fn(table.init, keys, iters=3)
    emit("update_build", sec_build, keys=n, keys_per_sec=f"{n / sec_build:.3e}")

    depth = 0
    while depth < args.max_depth:
        ins = jnp.asarray(rng.integers(0, n, size=batch, dtype=np.uint32))
        sec_ins = time_fn(state.insert, ins, iters=3)
        state = state.insert(ins)
        depth = state.epoch

        dels = jnp.asarray(rng.integers(0, n, size=64, dtype=np.uint32))
        sec_del = time_fn(state.delete, dels, iters=3)
        state = state.delete(dels)

        plan = table.plan_retrieve(state, queries)
        res = plan(state, queries)
        assert int(res.num_dropped) == 0, "benchmark capacity sizing bug"
        sec_q = time_fn(table.query, state, queries)
        sec_r = time_fn(plan, state, queries)
        emit(
            "update_depth",
            sec_r,
            depth=depth,
            insert_keys_per_sec=f"{batch / sec_ins:.3e}",
            delete_keys_per_sec=f"{64 / sec_del:.3e}",
            query_keys_per_sec=f"{queries.shape[0] / sec_q:.3e}",
            retrieve_keys_per_sec=f"{queries.shape[0] / sec_r:.3e}",
        )

        if depth in (1, args.max_depth // 2, args.max_depth):
            sec_c = time_fn(state.compact, iters=2)
            live = n + depth * batch
            emit(
                "update_compact",
                sec_c,
                depth=depth,
                live_keys=live,
                keys_per_sec=f"{live / sec_c:.3e}",
            )


if __name__ == "__main__":
    main()
