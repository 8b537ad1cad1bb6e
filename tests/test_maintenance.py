"""Incremental compaction — fold_oldest oracle grid, policy, stats, skew guard.

The fold contract: ``fold_oldest(state, k)`` followed by the remaining
deltas must answer every query exactly like the un-folded state AND like a
full ``compact()`` — across delete-then-reinsert histories whose tombstone
epochs straddle the fold boundary (the epoch-remap edge cases), at both
schema widths, on mesh1 and mesh8.  On a coherent stack the fold must also
be *layer-local*: zero collective rounds in the jitted executor (the
property that keeps background folds off the serving collective budget).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hashing, maintenance
from repro.core.maintenance import CompactionPolicy, TableStats, fold_oldest
from repro.core.schema import TableSchema
from repro.core.table import DistributedHashTable, retrieval_to_lists
from repro.obs.profiling import count_primitive
from test_table_state import Oracle, _keys_for, _value_rows, _values_for

SCHEMAS = [
    pytest.param(TableSchema("uint32", 1), id="u32x1"),
    pytest.param(TableSchema("uint64", 2), id="u64x2"),
]


def _deep_state(table, schema, rng, d):
    """base + 4 deltas with tombstones at epochs straddling any fold point.

    Deletes land at epochs 1, 3 and 4 (a delete's epoch is the delta count
    when it is issued), so a fold of k=2 must discard the epoch-1
    tombstone (spent inside the folded prefix) and keep/remap the later
    ones; reinserts after deletes keep the visibility rule honest.
    """
    n = 256
    keys = _keys_for(schema, rng, n)
    vals = _values_for(schema, 0, n)
    oracle = Oracle()
    oracle.insert(keys, vals)
    state = table.init(table.schema.pack_keys(keys), values=jnp.asarray(vals))

    batches = []
    for i in range(4):
        ins = _keys_for(schema, rng, 8 * d, lo=(1 << 16) + i * 4096, hi=(1 << 16) + (i + 1) * 4096)
        ins_vals = _values_for(schema, 10_000 + 1000 * i, len(ins))
        batches.append((ins, ins_vals))

    # epoch-1 tombstones: delete base rows after the first insert
    ins, ins_vals = batches[0]
    state = state.insert(table.schema.pack_keys(ins), jnp.asarray(ins_vals))
    oracle.insert(ins, ins_vals)
    dels1 = keys[:16]
    state = state.delete(table.schema.pack_keys(dels1))
    oracle.delete(dels1)

    ins, ins_vals = batches[1]
    state = state.insert(table.schema.pack_keys(ins), jnp.asarray(ins_vals))
    oracle.insert(ins, ins_vals)

    ins, ins_vals = batches[2]
    state = state.insert(table.schema.pack_keys(ins), jnp.asarray(ins_vals))
    oracle.insert(ins, ins_vals)
    # epoch-3 tombstones: hit base rows AND delta-1 rows
    dels3 = np.concatenate([keys[16:24], batches[0][0][: 2 * d]])
    state = state.delete(table.schema.pack_keys(dels3))
    oracle.delete(dels3)

    # reinsert some epoch-1-deleted keys in the LAST delta: visible again,
    # and the fold must keep them visible whichever side of the boundary
    # the tombstone lands on.
    re_keys = keys[:8]
    re_vals = _values_for(schema, 20_000, len(re_keys))
    state = state.insert(table.schema.pack_keys(re_keys), jnp.asarray(re_vals))
    oracle.insert(re_keys, re_vals)
    # epoch-4 tombstones on delta-2 rows
    dels4 = batches[2][0][: 2 * d]
    state = state.delete(table.schema.pack_keys(dels4))
    oracle.delete(dels4)

    queries = np.concatenate(
        [keys[:48], batches[0][0][: 2 * d], batches[2][0][: 4 * d], _keys_for(schema, rng, 2 * d)]
    )
    return state, oracle, queries


def _check(table, state, queries, oracle):
    q = table.schema.pack_keys(queries)
    counts = np.asarray(table.query(state, q))
    want = np.array([oracle.count(k) for k in queries], np.int32)
    np.testing.assert_array_equal(counts, want)
    res = table.retrieve(state, q, out_capacity=4096, seg_capacity=4096)
    assert int(res.num_dropped) == 0
    per_q = retrieval_to_lists(res)
    for i, k in enumerate(queries):
        got = sorted(_value_rows(np.asarray(per_q[i])), key=repr)
        assert got == oracle.values(k), f"query {i}"


@pytest.mark.parametrize("schema", SCHEMAS)
@pytest.mark.parametrize("meshname", ["mesh1", "mesh8"])
@pytest.mark.parametrize("k", [1, 2, 4])
def test_fold_oldest_matches_oracle_and_full_compact(schema, meshname, k, request):
    """fold_oldest(state, k) ∘ remaining deltas ≡ unfolded ≡ full compact()."""
    mesh = request.getfixturevalue(meshname)
    d = 8 if meshname == "mesh8" else 1
    table = DistributedHashTable(mesh, ("d",), hash_range=1 << 12, schema=schema)
    rng = np.random.default_rng(3 + d + schema.value_cols + k)
    state, oracle, queries = _deep_state(table, schema, rng, d)
    assert len(state.deltas) == 4

    folded = fold_oldest(state, k)
    assert len(folded.deltas) == 4 - k
    assert folded.coherent
    _check(table, folded, queries, oracle)

    # agreement with the full rebuild, and folds compose
    compacted = state.compact()
    _check(table, compacted, queries, oracle)
    refolded = fold_oldest(folded, 4 - k)  # fold the rest
    assert len(refolded.deltas) == 0
    _check(table, refolded, queries, oracle)


def test_fold_oldest_tombstone_remap(mesh8):
    """Tombstones spent inside the folded prefix are discarded; later ones
    shift down by k and keep hiding exactly the surviving deltas."""
    table = DistributedHashTable(mesh8, ("d",), hash_range=1 << 11)
    rng = np.random.default_rng(7)
    keys = rng.integers(0, 1 << 14, 256, dtype=np.uint32)
    state = table.init(jnp.asarray(keys))
    state = state.insert(jnp.asarray(rng.integers(1 << 14, 1 << 15, 8, dtype=np.uint32)))
    state = state.delete(jnp.asarray(keys[:4]))  # epoch 1: inside fold of k=2
    state = state.insert(jnp.asarray(rng.integers(1 << 14, 1 << 15, 8, dtype=np.uint32)))
    state = state.insert(jnp.asarray(rng.integers(1 << 14, 1 << 15, 8, dtype=np.uint32)))
    state = state.delete(jnp.asarray(keys[4:8]))  # epoch 3: survives fold of k=2
    assert int(state.tombstones.count) == 8

    folded = fold_oldest(state, 2)
    # epoch-1 entries discarded, epoch-3 entries remapped to 3-2=1
    assert int(folded.tombstones.count) == 4
    surviving = np.asarray(folded.tombstones.epochs)
    assert sorted(surviving[surviving >= 0].tolist()) == [1, 1, 1, 1]
    # the remap preserves semantics
    c = np.asarray(table.query(folded, jnp.asarray(keys[:8])))
    np.testing.assert_array_equal(c, np.zeros(8, np.int32))


def test_fold_zero_and_clamp(mesh8):
    table = DistributedHashTable(mesh8, ("d",), hash_range=1 << 11)
    rng = np.random.default_rng(11)
    state = table.init(jnp.asarray(rng.integers(0, 1 << 14, 256, dtype=np.uint32)))
    assert fold_oldest(state, 0) is state
    assert fold_oldest(state, 3) is state  # no deltas: clamps to identity
    state = state.insert(jnp.asarray(rng.integers(0, 1 << 14, 8, dtype=np.uint32)))
    folded = fold_oldest(state, 99)  # clamps to the delta depth
    assert len(folded.deltas) == 0


def test_fold_is_collective_free_on_coherent_stack(mesh8):
    """The serving guarantee: the jitted fold contains ZERO all_to_all
    primitives (a full compact pays a pre-balance + build exchange)."""
    table = DistributedHashTable(mesh8, ("d",), hash_range=1 << 12)
    rng = np.random.default_rng(13)
    keys = rng.integers(0, 1 << 14, 512, dtype=np.uint32)
    state = table.init(jnp.asarray(keys))
    for _ in range(3):
        state = state.insert(jnp.asarray(rng.integers(0, 1 << 14, 64, dtype=np.uint32)))
    state = state.delete(jnp.asarray(keys[:16]))

    jx = jax.make_jaxpr(lambda s: maintenance.exec_fold(table, s, k=2))(state)
    assert count_primitive(jx.jaxpr, "all_to_all") == 0
    # ... while the full compact does exchange (sanity: the comparison the
    # fold-vs-full bench is measuring is real)
    jc = jax.make_jaxpr(
        lambda s: table._compact_jit(s, capacity=1024, rebuild_rows=None)
    )(state)
    assert count_primitive(jc.jaxpr, "all_to_all") > 0


def test_fold_incoherent_falls_back_to_full_compact(mesh8):
    """Mixed-split stacks cannot fold locally: fold_oldest = compact()."""
    table = DistributedHashTable(
        mesh8, ("d",), hash_range=1 << 11, coherent_deltas=False
    )
    rng = np.random.default_rng(17)
    keys = rng.integers(0, 1 << 14, 256, dtype=np.uint32)
    state = table.init(jnp.asarray(keys))
    state = state.insert(jnp.asarray(rng.integers(0, 1 << 14, 16, dtype=np.uint32)))
    state = state.insert(jnp.asarray(rng.integers(0, 1 << 14, 16, dtype=np.uint32)))
    assert not state.coherent
    before = np.asarray(table.query(state, jnp.asarray(keys[:64])))
    folded = fold_oldest(state, 1)
    assert len(folded.deltas) == 0  # full fold
    np.testing.assert_array_equal(
        before, np.asarray(table.query(folded, jnp.asarray(keys[:64])))
    )


# ---------------------------------------------------------------------------
# CompactionPolicy + TableStats + should_compact shim
# ---------------------------------------------------------------------------


def _stats(**kw):
    base = dict(
        delta_depth=0,
        base_rows=1024,
        delta_rows=0,
        tombstone_count=0,
        tombstone_capacity=64,
        tombstone_dropped=0,
        num_dropped=0,
    )
    base.update(kw)
    return TableStats(**base)


def test_policy_triggers():
    p = CompactionPolicy(max_delta_depth=4, tombstone_load=0.5, max_dropped=10)
    assert not p.due(_stats())
    assert p.due(_stats(delta_depth=4))
    assert not p.due(_stats(delta_depth=3))
    assert p.due(_stats(tombstone_count=32))  # load 0.5
    assert not p.due(_stats(tombstone_count=31))
    assert p.due(_stats(tombstone_dropped=1))
    assert p.due(_stats(num_dropped=11))
    assert not p.due(_stats(num_dropped=10))
    # disabled triggers
    off = CompactionPolicy(max_delta_depth=None, tombstone_load=2.0, max_dropped=None, tombstone_overflow=False)
    assert not off.due(_stats(delta_depth=100, tombstone_dropped=5, num_dropped=999))


def test_policy_fold_amount_escalates():
    p = CompactionPolicy(max_delta_depth=8, fold_k=2)
    assert p.fold_amount(_stats(delta_depth=0)) == 0
    assert p.fold_amount(_stats(delta_depth=8)) == 2  # incremental
    assert p.fold_amount(_stats(delta_depth=1)) == 1  # clamped
    # tombstone pressure folds everything (frees the buffer)
    assert p.fold_amount(_stats(delta_depth=8, tombstone_dropped=1)) == 8
    assert p.fold_amount(_stats(delta_depth=8, tombstone_count=40)) == 8
    # escalation is orthogonal to depth: a saturated delete buffer needs
    # the full compact even when there are no deltas to fold
    assert not p.escalates(_stats(delta_depth=8))
    assert p.escalates(_stats(delta_depth=0, tombstone_count=40))
    assert p.escalates(_stats(delta_depth=0, tombstone_dropped=1))
    # dropped-rows pressure escalates too: incremental folds carry the drop
    # tally into the new base, only compact() rebuilds without it
    pd = CompactionPolicy(max_dropped=10)
    assert pd.escalates(_stats(delta_depth=0, num_dropped=11))
    assert pd.fold_amount(_stats(delta_depth=4, num_dropped=11)) == 4


def test_state_stats_and_should_compact_shim(mesh8):
    table = DistributedHashTable(
        mesh8, ("d",), hash_range=1 << 10, max_deltas=2, tombstone_capacity=16
    )
    rng = np.random.default_rng(19)
    state = table.init(jnp.asarray(rng.integers(0, 1 << 14, 256, dtype=np.uint32)))
    st = state.stats()
    assert st.delta_depth == 0 and st.base_rows > 0
    assert st.tombstone_capacity == 0 and st.tombstone_load == 0.0
    assert not state.should_compact()

    state = state.delete(jnp.asarray(rng.integers(0, 1 << 14, 8, dtype=np.uint32)))
    st = state.stats()
    assert st.tombstone_count == 8 and st.tombstone_capacity == 16
    assert state.should_compact(tombstone_load=0.5)
    assert not state.should_compact(tombstone_load=0.9)

    for _ in range(2):
        state = state.insert(jnp.asarray(rng.integers(0, 1 << 14, 8, dtype=np.uint32)))
    assert state.stats().delta_depth == 2
    assert state.should_compact(tombstone_load=1.1)  # ring full alone
    assert not state.should_compact(tombstone_load=1.1, ring_full=False)


# ---------------------------------------------------------------------------
# Delta-dispatch skew guard
# ---------------------------------------------------------------------------


def _narrow_batch(table, state, n):
    """Distinct keys whose base-space hash all lands in ONE owner's range."""
    splits = np.asarray(state.base.hash_splits)
    cand = np.arange(1 << 16, 1 << 18, dtype=np.uint32)
    h = np.asarray(
        hashing.hash_to_buckets(jnp.asarray(cand), table.hash_range, seed=table.seed)
    )
    narrow = cand[h < splits[1]][:n]
    assert len(narrow) == n
    return narrow


def test_skew_guard_falls_back_instead_of_dropping(mesh8):
    """A hash-range-skewed insert would overflow the frozen-splits dispatch;
    the guard routes it to an incoherent delta with zero dropped rows."""
    table = DistributedHashTable(mesh8, ("d",), hash_range=1 << 12)
    rng = np.random.default_rng(23)
    keys = rng.integers(0, 1 << 14, 512, dtype=np.uint32)
    state = table.init(jnp.asarray(keys))
    narrow = _narrow_batch(table, state, 512)

    assert table.skew_fallbacks == 0
    s2 = table.insert(state, jnp.asarray(narrow))
    assert table.skew_fallbacks == 1
    assert not s2.coherent  # legacy-routed delta
    assert int(s2.num_dropped) == 0  # the point: no rows lost
    c = np.asarray(table.query(s2, jnp.asarray(narrow[:64])))
    assert (c >= 1).all()

    # a well-spread insert does NOT trip the guard
    s3 = table.insert(state, jnp.asarray(rng.integers(0, 1 << 14, 512, dtype=np.uint32)))
    assert table.skew_fallbacks == 1
    assert s3.coherent


def test_skew_guard_off_reproduces_drops(mesh8):
    """Without the guard the same batch drops rows (the ROADMAP failure)."""
    table = DistributedHashTable(mesh8, ("d",), hash_range=1 << 12, skew_guard=False)
    rng = np.random.default_rng(23)
    keys = rng.integers(0, 1 << 14, 512, dtype=np.uint32)
    state = table.init(jnp.asarray(keys))
    narrow = _narrow_batch(table, state, 512)
    s2 = table.insert(state, jnp.asarray(narrow))
    assert s2.coherent and int(s2.num_dropped) > 0
    assert table.skew_fallbacks == 0
