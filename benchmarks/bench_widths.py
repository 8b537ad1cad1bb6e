"""Schema-width benchmark — uint32 vs uint64 keys, 1 vs 4 value columns.

Sweeps the :class:`~repro.core.schema.TableSchema` grid and reports
per-key build/query/retrieve throughput so the cost of the two-lane
64-bit key packing and of multi-column payload movement is a number, not
a guess.  WarpCore/WarpSpeed treat configurable key/value widths as
table-stakes for a reusable GPU hash table; this is the TPU-side scorecard.
"""
import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--keys", type=int, default=1 << 17)
    ap.add_argument("--dup", type=int, default=4, help="average key multiplicity")
    args = ap.parse_args()

    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.common import emit, time_fn
    from repro.core.schema import TableSchema, pack_u64
    from repro.core.table import DistributedHashTable, table_mesh

    d = len(jax.devices())
    mesh = table_mesh()
    n = (args.keys // d) * d
    rng = np.random.default_rng(1)
    distinct = max(1, n // args.dup)

    for key_dtype in ("uint32", "uint64"):
        for value_cols in (1, 4):
            sch = TableSchema(key_dtype, value_cols)
            if key_dtype == "uint64":
                # spread keys across the full 64-bit range so the two-lane
                # compare/hash path is actually exercised
                raw = rng.integers(0, distinct, size=n).astype(np.uint64)
                raw |= raw << np.uint64(32)
                keys = pack_u64(raw)
            else:
                keys = jnp.asarray(
                    rng.integers(0, distinct, size=n, dtype=np.uint32)
                )
            if value_cols == 1:
                values = jnp.arange(n, dtype=jnp.int32)
            else:
                values = jnp.asarray(
                    rng.integers(-(1 << 20), 1 << 20, size=(n, value_cols)).astype(
                        np.int32
                    )
                )
            table = DistributedHashTable(
                mesh, ("d",), hash_range=n, capacity_slack=2.0, schema=sch
            )
            state = table.build(keys, values=values)
            out_cap = 8 * ((4 * args.dup * (n // d) + 64) // 8)

            def run_build():
                return table.build(keys, values=values)

            def run_retrieve(state, q):
                return table.retrieve(
                    state, q, out_capacity=out_cap, seg_capacity=out_cap
                )

            res = run_retrieve(state, keys)
            assert int(res.num_dropped) == 0, "benchmark capacity sizing bug"
            sec_b = time_fn(run_build)
            sec_q = time_fn(table.query, state, keys)
            sec_r = time_fn(run_retrieve, state, keys)
            results = int(np.asarray(res.counts).sum())
            emit(
                "widths",
                sec_r,
                key_dtype=key_dtype,
                value_cols=value_cols,
                keys=n,
                results=results,
                build_keys_per_sec=f"{n / sec_b:.3e}",
                query_keys_per_sec=f"{n / sec_q:.3e}",
                retrieve_keys_per_sec=f"{n / sec_r:.3e}",
                retrieve_results_per_sec=f"{results / sec_r:.3e}",
            )


if __name__ == "__main__":
    main()
