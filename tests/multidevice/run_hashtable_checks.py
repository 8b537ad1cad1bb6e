"""Multi-device HashGraph correctness checks.

Run in a subprocess with fake host devices, e.g.::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 python tests/multidevice/run_hashtable_checks.py

Exits non-zero on any failure; prints OK lines per check.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import sys
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.table import DistributedHashTable
from repro.launch.mesh import auto_mesh


def check(name, cond):
    if not cond:
        print(f"FAIL {name}")
        sys.exit(1)
    print(f"OK {name}")


def np_counts(build_keys, query_keys):
    c = Counter(build_keys.tolist())
    return np.array([c[int(q)] for q in query_keys], dtype=np.int32)


def main():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 fake devices, got {len(devs)}"
    mesh = auto_mesh((2, 4), ("x", "y"))
    rng = np.random.default_rng(0)

    # ---- 1. random keys, exact multiset counts ----------------------------
    n = 1 << 13
    hr = n  # C = 1 as in the paper
    keys = rng.integers(0, 1 << 20, size=n, dtype=np.uint32)
    queries = np.concatenate(
        [keys[: n // 2], rng.integers(0, 1 << 20, size=n // 2, dtype=np.uint32)]
    )
    table = DistributedHashTable(mesh, ("x", "y"), hash_range=hr)
    state = table.build(jnp.asarray(keys))
    check("no_capacity_drops", int(state.num_dropped) == 0)
    counts = np.asarray(table.query(state, jnp.asarray(queries)))
    check("random_counts_exact", (counts == np_counts(keys, queries)).all())

    # ---- 2. sequential keys (paper's sequential experiment) ----------------
    keys_seq = np.arange(n, dtype=np.uint32)
    state2 = table.build(jnp.asarray(keys_seq))
    counts2 = np.asarray(table.query(state2, jnp.asarray(keys_seq)))
    check("sequential_all_found_once", (counts2 == 1).all())

    # ---- 3. heavy duplicates (paper §5.4) ----------------------------------
    dup = 64
    base = rng.integers(0, 1 << 18, size=n // dup, dtype=np.uint32)
    keys_dup = np.repeat(base, dup)
    rng.shuffle(keys_dup)
    # generous capacity slack: duplicates concentrate keys on fewer devices
    table_dup = DistributedHashTable(mesh, ("x", "y"), hash_range=hr, capacity_slack=1.5)
    state3 = table_dup.build(jnp.asarray(keys_dup))
    check("dup_no_drops", int(state3.num_dropped) == 0)
    q3 = np.concatenate([base, rng.integers(0, 1 << 18, size=64, dtype=np.uint32)])
    pad = (-len(q3)) % 8
    q3 = np.concatenate([q3, np.full(pad, base[0], np.uint32)])
    counts3 = np.asarray(table_dup.query(state3, jnp.asarray(q3)))
    check("dup_counts_exact", (counts3 == np_counts(keys_dup, q3)).all())

    # ---- 4. join size -------------------------------------------------------
    jsz = int(table.join_size(state, jnp.asarray(queries)))
    check("join_size", jsz == int(np_counts(keys, queries).sum()))

    # ---- 5. paper-faithful probe path matches sorted path -------------------
    table_probe = DistributedHashTable(
        mesh, ("x", "y"), hash_range=hr, paper_faithful_probe=True, max_probe=64
    )
    state5 = table_probe.build(jnp.asarray(keys))
    counts5 = np.asarray(table_probe.query(state5, jnp.asarray(queries)))
    check("probe_matches_sorted", (counts5 == counts).all())

    # ---- 6. load balance: each device holds ~N/D keys ----------------------
    sizes = []
    d = 8
    off_g = np.asarray(state.local.offsets).reshape(d, -1)
    for r in range(d):
        sizes.append(int(off_g[r][table.local_range_cap]))
    sizes = np.array(sizes)
    imbalance = sizes.max() / max(1.0, n / d)
    check("load_balanced<=1.25x", imbalance <= 1.25)
    check("all_keys_distributed", sizes.sum() == n)

    # ---- 7. single-axis mesh (flat 8) ---------------------------------------
    mesh1 = auto_mesh((8,), ("d",))
    t1 = DistributedHashTable(mesh1, ("d",), hash_range=hr)
    s1 = t1.build(jnp.asarray(keys))
    c1 = np.asarray(t1.query(s1, jnp.asarray(queries)))
    check("flat_mesh_counts_exact", (c1 == np_counts(keys, queries)).all())

    print("ALL_OK")


if __name__ == "__main__":
    main()
