"""Quickstart: build and query a HashGraph, single- and multi-device.

    PYTHONPATH=src python examples/quickstart.py
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hashgraph
from repro.core.table import DistributedHashTable, table_mesh


def main() -> None:
    rng = np.random.default_rng(0)
    n = 1 << 14
    keys = jnp.asarray(rng.integers(0, n, size=n, dtype=np.uint32))
    queries = jnp.asarray(rng.integers(0, n, size=n, dtype=np.uint32))

    # ---- single-device (paper Alg. 1, TPU-native build) --------------------
    hg = hashgraph.build(keys, table_size=n)  # C = 1
    counts = hashgraph.query_count_sorted(hg, queries)
    print(f"single-device: {int(jnp.sum(counts > 0))}/{n} queries hit, "
          f"join size {int(jnp.sum(counts))}")

    # ---- multi-device (paper Alg. 2: bin, split, all-to-all, build) --------
    d = len(jax.devices())
    mesh = table_mesh()
    table = DistributedHashTable(mesh, ("d",), hash_range=n)
    state = table.build(keys)
    dcounts = table.query(state, queries)
    assert (np.asarray(dcounts) == np.asarray(counts)).all(), "mismatch!"
    print(f"multi-device ({d} devices): identical counts, "
          f"join size {int(table.join_size(state, queries))}, "
          f"0 capacity drops = {int(state.num_dropped) == 0}")


if __name__ == "__main__":
    main()
