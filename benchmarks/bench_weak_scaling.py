"""Paper Fig. 3 — weak scaling: fixed keys/device, growing device count.

Run by ``benchmarks.run`` in a subprocess per device count (the device
count is locked at jax init).  Reports build and query throughput
(keys/s) for random and sequential keys, as in the paper.
"""
import argparse
import os


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--keys-per-device", type=int, default=1 << 18)
    ap.add_argument("--devices", type=int, default=0, help="0 = use all present")
    args = ap.parse_args()

    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.devices and "XLA_FLAGS" not in os.environ:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}"
        )

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.common import emit, time_fn
    from repro.core.table import DistributedHashTable, table_mesh

    d = len(jax.devices())
    n = args.keys_per_device * d
    mesh = table_mesh()
    table = DistributedHashTable(mesh, ("d",), hash_range=n)
    rng = np.random.default_rng(0)

    for dist in ("random", "sequential"):
        if dist == "random":
            keys = jnp.asarray(rng.integers(0, n, size=n, dtype=np.uint32))
        else:
            keys = jnp.arange(n, dtype=jnp.uint32)
        sec = time_fn(table.build, keys)
        emit(
            f"weak_scaling_build_{dist}",
            sec,
            devices=d,
            keys=n,
            keys_per_sec=f"{n / sec:.3e}",
        )
        state = table.build(keys)
        sec = time_fn(table.query, state, keys)
        emit(
            f"weak_scaling_query_{dist}",
            sec,
            devices=d,
            keys=n,
            keys_per_sec=f"{n / sec:.3e}",
        )


if __name__ == "__main__":
    main()
