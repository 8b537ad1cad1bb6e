"""Database inner-join via the distributed HashGraph (paper's headline app).

Two relations R(key, payload) and S(key, payload).  The join is *materialized*
through the retrieval subsystem: ``inner_join`` returns every matched
``(S row, R row)`` pair, and ``retrieve`` returns the full CSR of R-rows per
probe key — both verified against a numpy dict-of-lists oracle.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/inner_join.py
"""
from collections import defaultdict

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hashgraph
from repro.core.table import (
    DistributedHashTable,
    join_to_pairs,
    retrieval_to_lists,
    table_mesh,
)


def main() -> None:
    rng = np.random.default_rng(7)
    n_r, n_s = 1 << 15, 1 << 14
    # R: build side (fact table); S: probe side, 50% of keys overlap
    r_keys = rng.integers(0, 1 << 16, size=n_r, dtype=np.uint32)
    s_keys = np.concatenate(
        [
            rng.choice(r_keys, size=n_s // 2),
            rng.integers(1 << 16, 1 << 17, size=n_s // 2).astype(np.uint32),
        ]
    )
    rng.shuffle(s_keys)

    d = len(jax.devices())
    mesh = table_mesh()
    table = DistributedHashTable(mesh, ("d",), hash_range=n_r)
    # values = R row ids ride through the exchange for the join payload
    state = table.build(
        jnp.asarray(r_keys), values=jnp.arange(n_r, dtype=jnp.int32)
    )

    # numpy oracle: key -> list of R row ids
    oracle = defaultdict(list)
    for row, k in enumerate(r_keys.tolist()):
        oracle[k].append(row)
    expect_pairs = sorted(
        (i, r) for i, k in enumerate(s_keys) for r in oracle[int(k)]
    )

    # --- join cardinality (counting path) ---------------------------------
    join_size = int(table.join_size(state, jnp.asarray(s_keys)))
    assert join_size == len(expect_pairs), (join_size, len(expect_pairs))
    print(f"|R ⋈ S| = {join_size} (verified), R={n_r} S={n_s} devices={d}")

    # --- materialized join (retrieval path) -------------------------------
    cap = 8 * ((2 * len(expect_pairs) // d + 64) // 8)
    join = table.inner_join(
        state, jnp.asarray(s_keys), out_capacity=cap, seg_capacity=cap
    )
    assert int(join.num_dropped) == 0, "raise out_capacity/seg_capacity"
    pairs = join_to_pairs(join)
    assert sorted(map(tuple, pairs.tolist())) == expect_pairs
    print(f"materialized {len(pairs)} (S row, R row) pairs (verified)")

    # --- CSR retrieval of all matching R rows per probe key ---------------
    res = table.retrieve(
        state, jnp.asarray(s_keys), out_capacity=cap, seg_capacity=cap
    )
    assert int(res.num_dropped) == 0
    per_query = retrieval_to_lists(res)
    for i in range(0, n_s, n_s // 7):
        assert sorted(np.asarray(per_query[i]).tolist()) == sorted(
            oracle[int(s_keys[i])]
        )
    sample = [np.asarray(per_query[i]).tolist() for i in range(4)]
    print("probe sample → matching R rows:", sample)

    # membership + first-match row id (single-device API, unchanged)
    hg = hashgraph.build(jnp.asarray(r_keys), table_size=n_r)
    rows = hashgraph.lookup_first(hg, jnp.asarray(s_keys[:8]))
    print("probe sample → first matching R row:", np.asarray(rows))


if __name__ == "__main__":
    main()
