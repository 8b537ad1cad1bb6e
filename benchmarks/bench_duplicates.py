"""Paper Fig. 4 — duplicate-keys sweep: throughput vs avg key occurrence.

Fixed key count; the hash range shrinks 2^0..2^6 so the average
multiplicity doubles each step (paper: build flat, query decays once
lists exceed ~8 — our sorted-bucket query keeps the decay logarithmic,
the beyond-paper variant is reported alongside the faithful probe).
"""
import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--keys", type=int, default=1 << 19)
    ap.add_argument("--max-dup-log2", type=int, default=6)
    args = ap.parse_args()

    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    import jax.numpy as jnp
    import numpy as np

    from benchmarks.common import emit, time_fn
    from repro.core.table import DistributedHashTable, table_mesh

    mesh = table_mesh()
    n = args.keys
    rng = np.random.default_rng(1)

    for dup_log2 in range(args.max_dup_log2 + 1):
        dup = 1 << dup_log2
        # sample keys from a range n/dup wide → avg multiplicity ≈ dup
        keys = jnp.asarray(rng.integers(0, max(1, n // dup), size=n, dtype=np.uint32))
        hr = n  # C=1 table size, as the paper fixes it
        table = DistributedHashTable(
            mesh, ("d",), hash_range=hr, capacity_slack=1.5
        )
        sec_b = time_fn(table.build, keys)
        state = table.build(keys)
        sec_q = time_fn(table.query, state, keys)
        table_p = DistributedHashTable(
            mesh, ("d",), hash_range=hr, capacity_slack=1.5,
            paper_faithful_probe=True, max_probe=int(dup * 8 + 16),
        )
        state_p = table_p.build(keys)
        sec_qp = time_fn(table_p.query, state_p, keys)
        emit(
            "duplicates",
            sec_b,
            avg_occurrence=dup,
            build_keys_per_sec=f"{n / sec_b:.3e}",
            query_sorted_keys_per_sec=f"{n / sec_q:.3e}",
            query_probe_keys_per_sec=f"{n / sec_qp:.3e}",
        )


if __name__ == "__main__":
    main()
