"""Mutable distributed table — the plan/execute API over versioned state.

Builds a table, inserts a batch, deletes some keys, re-inserts one of
them, and retrieves — first eagerly, then as ONE jitted program built
around a pre-sized plan (zero device→host syncs after planning), and
finally compacts the deltas + tombstones back into a single base graph.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/mutable_table.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core.table import DistributedHashTable, retrieval_to_lists, table_mesh


def main() -> None:
    rng = np.random.default_rng(0)
    mesh = table_mesh()
    n = 1 << 12

    table = DistributedHashTable(mesh, ("d",), hash_range=n)
    keys = jnp.asarray(rng.integers(0, n, size=n, dtype=np.uint32))
    values = jnp.arange(n, dtype=jnp.int32)

    # ---- eager mutation flow ----------------------------------------------
    state = table.init(keys, values)  # versioned TableState
    fresh = jnp.asarray(rng.integers(n, 2 * n, size=64, dtype=np.uint32))
    state = state.insert(fresh, jnp.arange(n, n + 64, dtype=jnp.int32))
    state = state.delete(keys[:32])  # tombstones: hides base rows
    state = state.insert(keys[:8], jnp.arange(9000, 9008, dtype=jnp.int32))
    print(f"epoch {state.epoch} (deltas), drops {int(state.num_dropped)}")

    queries = jnp.concatenate([keys[:64], fresh[:32], keys[100:132]])
    plan = table.plan_retrieve(state, queries)  # counts round sizes caps
    res = plan(state, queries)
    lists = retrieval_to_lists(res)
    print(
        f"planned caps out={plan.out_capacity} seg={plan.seg_capacity}; "
        f"query 0 -> {np.asarray(lists[0]).tolist()} "
        f"(deleted key, reinserted value only)"
    )

    # ---- the same flow as one jitted program ------------------------------
    @jax.jit
    def program(k, v, ins_k, ins_v, dead):
        st = table.init(k, v)
        st = st.insert(ins_k, ins_v)
        st = st.delete(dead)
        return plan(st, queries)

    res2 = program(keys, values, fresh, jnp.arange(64, dtype=jnp.int32), keys[:32])
    print(f"jitted program: drops {int(res2.num_dropped)}")

    # ---- compaction: fold deltas + tombstones into a fresh base -----------
    # capacity=None sizes the rebuild from a live-count round (rows that
    # survive the fold), so steady update/compact cycles keep the base flat.
    compacted = state.compact()
    assert compacted.epoch == 0
    same = np.array_equal(
        np.asarray(table.query(state, queries)),
        np.asarray(table.query(compacted, queries)),
    )
    print(f"compacted: 1 layer again, answers identical = {same}")

    # ---- auto-compaction: fold when the state says it is due ---------------
    # should_compact() fires on a full delta ring, a tombstone-load
    # threshold, or tombstone overflow; insert(..., auto_compact=True)
    # folds first instead of raising "delta ring full".  Every read path
    # stays single-route (one exchange round per query/retrieve, whatever
    # the delta depth) because inserts build deltas on the base's splits.
    state = compacted
    for step in range(3 * table.max_deltas):
        batch = jnp.asarray(rng.integers(0, n, size=64, dtype=np.uint32))
        state = state.insert(batch, auto_compact=True)  # never raises
    print(
        f"after {3 * table.max_deltas} auto-compacting inserts: "
        f"epoch {state.epoch}, should_compact={state.should_compact()}"
    )


if __name__ == "__main__":
    main()
