"""Paper §5 SOTA comparison table.

Prints the paper-reported throughputs next to this implementation's
measured numbers (CPU here — indicative only; the same harness reports
TPU keys/s when run on real hardware).
"""
import argparse

PAPER = [
    ("Folklore CPU [Maier et al.]", "multicore CPU", 0.3e9),
    ("Balkesen et al.", "multicore CPU", 0.45e9),
    ("Cray XMT [Goodman et al.]", "massively-threaded", 0.25e9),
    ("Barthels et al. 512 cores", "distributed MPI", 8e9),
    ("Barthels et al. 1024 cores", "distributed MPI", 10e9),
    ("Single-GPU HashGraph [Green]", "V100", 2.3e9),
    ("Multi-GPU HashGraph (paper, DGX-2 16xV100)", "NVSwitch", 8e9),
    ("Multi-GPU HashGraph (paper, AC922 6xV100)", "NVLink", 5e9),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--keys", type=int, default=1 << 19)
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
    rng = np.random.default_rng(4)
    keys = jnp.asarray(rng.integers(0, n, size=n, dtype=np.uint32))
    table = DistributedHashTable(mesh, ("d",), hash_range=n)
    sec = time_fn(table.build, keys)
    ours = n / sec

    print(f"{'system':52s} {'class':22s} {'build keys/s':>14s}")
    for name, klass, thr in PAPER:
        print(f"{name:52s} {klass:22s} {thr:14.2e}")
    print(f"{'THIS IMPL (CPU, ' + str(d) + ' fake devices)':52s} {'JAX/TPU-target':22s} {ours:14.2e}")
    emit("sota_build", sec, keys=n, keys_per_sec=f"{ours:.3e}", devices=d)


if __name__ == "__main__":
    main()
