"""Paper Fig. 5 — normalized execution time of the four build phases.

Cumulative jitted prefixes (phase1, phases1-2, phases1-3, full build);
per-phase time is the successive difference — the standard way to carve a
fused SPMD program without instrumenting inside jit.
"""
import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--keys", type=int, default=1 << 19)
    args = ap.parse_args()

    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.utils.compat import shard_map
    from jax.sharding import PartitionSpec as P

    from benchmarks.common import emit, time_fn
    from repro.core import exchange, hashing, multi_hashgraph, partition
    from repro.core.hashgraph import EMPTY_KEY
    from repro.core import hashgraph
    from repro.core.table import table_mesh

    d = len(jax.devices())
    mesh = table_mesh()
    ax = ("d",)
    n = args.keys
    hr = n
    rng = np.random.default_rng(2)
    keys = jnp.asarray(rng.integers(0, n, size=n, dtype=np.uint32))
    bins_g = partition.choose_num_bins(hr, d)
    capacity = multi_hashgraph.default_capacity(n // d, d, 1.25)
    local_cap = int(-(-hr // d) * 1.5)

    def phase1(k):
        h = hashing.hash_to_buckets(k, hr)
        hist = partition.local_bin_histogram(h, bins_g, hr)
        ghist = jax.lax.psum(hist, ax)
        return partition.balanced_hash_splits(ghist, d, hr)

    def phase12(k):
        splits = phase1(k)
        h = hashing.hash_to_buckets(k, hr)
        dest = partition.destination_of(h, splits)
        packed, _ = exchange.pack_by_destination(
            (k,), dest, d, capacity, fills=(jnp.uint32(EMPTY_KEY),)
        )
        return packed[0]

    def phase123(k):
        buf = phase12(k)
        b = buf.reshape(d, capacity)
        return exchange.all_to_all_hierarchical(b, ax).reshape(-1)

    def phase1234(k):
        rk = phase123(k)
        splits = phase1(k)
        rank = exchange.my_rank(ax)
        lo = splits[rank]
        buckets = multi_hashgraph._local_buckets(rk, lo, hr, local_cap, hashing.DEFAULT_SEED)
        hg = hashgraph.build_from_buckets(rk, buckets, local_cap)
        return hg.offsets

    def sm(f, out_spec):
        return jax.jit(
            shard_map(
                f, mesh=mesh, in_specs=(P(ax),), out_specs=out_spec, check_vma=False
            )
        )

    fns = {
        "partitioning": sm(phase1, P()),
        "preprocess": sm(phase12, P(ax)),
        "all_to_all": sm(phase123, P(ax)),
        "table_construction": sm(phase1234, P(ax)),
    }
    prev = 0.0
    total = None
    for name, fn in fns.items():
        sec = time_fn(fn, keys)
        emit(f"phase_cumulative_{name}", sec, keys=n, devices=d)
        emit(f"phase_delta_{name}", max(sec - prev, 0.0), keys=n, devices=d)
        prev = sec
        total = sec
    emit("phase_total_build", total, keys=n, devices=d,
         keys_per_sec=f"{n / total:.3e}")


if __name__ == "__main__":
    main()
