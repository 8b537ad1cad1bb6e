"""Single-route layered execution — collective-count regression + parity.

The tentpole invariant of the fused path: a partition-coherent delta stack
(every delta built on the base's frozen ``hash_splits``) executes query /
retrieve / plan in ONE exchange round regardless of delta depth — one
query-dispatch all-to-all plus one fused ragged return — where the
per-layer legacy path pays one round per layer.

* ``test_collective_count_regression`` counts ``all_to_all`` primitives in
  the traced executors: an L=4-layer retrieve must contain exactly one
  dispatch and one ragged return (2 collectives) on the fused path vs 2·L
  on the legacy path — so a routing-round regression fails loudly in CI.
* The parity grid runs identical mutation histories (including
  delete-then-reinsert epochs) through the fused path, the forced-legacy
  path on the same coherent state, and a mixed-split legacy stack
  (``coherent_deltas=False``, exercising the fallback), across
  uint32/uint64 keys × 1/2 value columns on mesh1 and mesh8.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import plans
from repro.core.schema import TableSchema
from repro.core.table import (
    DistributedHashTable,
    join_to_pairs,
    retrieval_to_lists,
)
from repro.obs.profiling import count_primitive
from test_table_state import Oracle, _keys_for, _value_rows, _values_for

SCHEMAS = [
    pytest.param(TableSchema("uint32", 1), id="u32x1"),
    pytest.param(TableSchema("uint64", 2), id="u64x2"),
]


def _four_layer_state(table, rng):
    """base + 3 deltas = an L=4 layer stack with tombstones."""
    keys = rng.integers(0, 1 << 14, 512, dtype=np.uint32)
    state = table.init(jnp.asarray(keys))
    for _ in range(3):
        state = state.insert(
            jnp.asarray(rng.integers(0, 1 << 14, 64, dtype=np.uint32))
        )
    state = state.delete(jnp.asarray(keys[:16]))
    return state


def test_collective_count_regression(mesh8):
    """L=4 retrieve: ONE dispatch a2a + ONE ragged return, depth-independent.

    The legacy per-layer path pays 2 collectives per layer; the fused path
    must stay at 2 total (the acceptance bound of the single-route issue).
    Query likewise: 2 fused vs 2·L legacy.
    """
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.integers(0, 1 << 14, 128, dtype=np.uint32))

    fused_t = DistributedHashTable(mesh8, ("d",), hash_range=1 << 12)
    legacy_t = DistributedHashTable(
        mesh8, ("d",), hash_range=1 << 12, fused_routing=False
    )
    nlayers = 4
    for table, want_per_op in [(fused_t, 2), (legacy_t, 2 * nlayers)]:
        state = _four_layer_state(table, np.random.default_rng(5))
        assert len(state.layers) == nlayers

        jx = jax.make_jaxpr(
            lambda s, qq, t=table: plans.exec_retrieve(
                t, s, qq, out_capacity=2048, seg_capacity=2048
            )
        )(state, q)
        assert count_primitive(jx.jaxpr, "all_to_all") == want_per_op

        jq = jax.make_jaxpr(
            lambda s, qq, t=table: plans.exec_query(t, s, qq)
        )(state, q)
        assert count_primitive(jq.jaxpr, "all_to_all") == want_per_op

    # The planning counts round is also single-route on the fused path.
    state = _four_layer_state(fused_t, np.random.default_rng(5))
    jp = jax.make_jaxpr(lambda s, qq: plans.exec_plan_caps(fused_t, s, qq))(
        state, q
    )
    assert count_primitive(jp.jaxpr, "all_to_all") == 1  # dispatch only


def test_depth_independence_of_collective_count(mesh8):
    """Fused collective count is flat in L: identical at 1, 2, 4, 8 layers."""
    table = DistributedHashTable(mesh8, ("d",), hash_range=1 << 12, max_deltas=8)
    rng = np.random.default_rng(9)
    q = jnp.asarray(rng.integers(0, 1 << 14, 128, dtype=np.uint32))
    state = table.init(jnp.asarray(rng.integers(0, 1 << 14, 512, dtype=np.uint32)))
    counts = []
    for depth in range(8):
        if len(state.layers) in (1, 2, 4, 8):
            jx = jax.make_jaxpr(
                lambda s, qq: plans.exec_retrieve(
                    table, s, qq, out_capacity=2048, seg_capacity=2048
                )
            )(state, q)
            counts.append(count_primitive(jx.jaxpr, "all_to_all"))
        state = state.insert(
            jnp.asarray(rng.integers(0, 1 << 14, 64, dtype=np.uint32))
        )
    assert counts == [2, 2, 2, 2]


# ---------------------------------------------------------------------------
# fused vs legacy parity
# ---------------------------------------------------------------------------


def _mutation_history(table, schema, rng, d):
    """build → insert → delete → reinsert, mirrored into an oracle."""
    n = 256
    keys = _keys_for(schema, rng, n)
    vals = _values_for(schema, 0, n)
    oracle = Oracle()
    oracle.insert(keys, vals)
    state = table.init(table.schema.pack_keys(keys), values=jnp.asarray(vals))

    ins = _keys_for(schema, rng, 8 * d, lo=1 << 16, hi=1 << 17)
    ins_vals = _values_for(schema, 10_000, len(ins))
    state = state.insert(table.schema.pack_keys(ins), jnp.asarray(ins_vals))
    oracle.insert(ins, ins_vals)

    dels = np.concatenate([keys[:16], ins[: 2 * d]])
    state = state.delete(table.schema.pack_keys(dels))
    oracle.delete(dels)

    # delete-then-reinsert: later epochs stay visible through the tombstones
    re_keys = keys[:8]
    re_vals = _values_for(schema, 20_000, len(re_keys))
    state = state.insert(table.schema.pack_keys(re_keys), jnp.asarray(re_vals))
    oracle.insert(re_keys, re_vals)

    queries = np.concatenate([keys[:64], ins[: 2 * d], _keys_for(schema, rng, 2 * d)])
    return state, oracle, queries


def _observe(table, state, queries):
    q = table.schema.pack_keys(queries)
    counts = np.asarray(table.query(state, q)).tolist()
    res = table.retrieve(state, q, out_capacity=4096, seg_capacity=4096)
    assert int(res.num_dropped) == 0
    lists = [
        sorted(_value_rows(np.asarray(v)), key=repr)
        for v in retrieval_to_lists(res)
    ]
    join = table.inner_join(state, q, out_capacity=4096, seg_capacity=4096)
    pairs = sorted(map(tuple, join_to_pairs(join).tolist()))
    return counts, lists, pairs, int(table.join_size(state, q))


@pytest.mark.parametrize("schema", SCHEMAS)
@pytest.mark.parametrize("meshname", ["mesh1", "mesh8"])
def test_fused_vs_legacy_parity(schema, meshname, request):
    """Identical mutation history through three routings, one oracle.

    1. fused single-route on a coherent stack (the default),
    2. forced per-layer legacy on the SAME coherent state
       (``fused_routing=False``),
    3. a mixed-split legacy stack (``coherent_deltas=False``) exercising
       the automatic fallback.
    All three must agree with each other and the oracle.
    """
    mesh = request.getfixturevalue(meshname)
    d = 8 if meshname == "mesh8" else 1
    variants = {
        "fused": {},
        "forced-legacy": {"fused_routing": False},
        "mixed-splits": {"coherent_deltas": False},
    }
    observed = {}
    for label, kw in variants.items():
        table = DistributedHashTable(
            mesh, ("d",), hash_range=1 << 12, schema=schema, **kw
        )
        rng = np.random.default_rng(17 + d + schema.value_cols)
        state, oracle, queries = _mutation_history(table, schema, rng, d)
        assert state.coherent == (label != "mixed-splits")
        counts, lists, pairs, jsize = _observe(table, state, queries)
        want = [oracle.count(k) for k in queries]
        assert counts == want, label
        for i, k in enumerate(queries):
            assert lists[i] == oracle.values(k), f"{label}: query {i}"
        observed[label] = (counts, lists, pairs, jsize)
    assert observed["fused"] == observed["forced-legacy"]
    assert observed["fused"] == observed["mixed-splits"]


def test_mixed_split_stack_uses_per_layer_routing(mesh8):
    """The mixed-split fallback really is per-layer: 2·L collectives."""
    table = DistributedHashTable(
        mesh8, ("d",), hash_range=1 << 12, coherent_deltas=False
    )
    rng = np.random.default_rng(23)
    state = table.init(jnp.asarray(rng.integers(0, 1 << 14, 512, dtype=np.uint32)))
    for _ in range(2):
        state = state.insert(
            jnp.asarray(rng.integers(0, 1 << 14, 64, dtype=np.uint32))
        )
    assert not state.coherent
    q = jnp.asarray(rng.integers(0, 1 << 14, 128, dtype=np.uint32))
    jx = jax.make_jaxpr(
        lambda s, qq: plans.exec_retrieve(
            table, s, qq, out_capacity=2048, seg_capacity=2048
        )
    )(state, q)
    assert count_primitive(jx.jaxpr, "all_to_all") == 2 * len(state.layers)


def test_fused_plan_caps_are_exact(mesh8):
    """Fused planning sizes the fused execution with zero drops and an
    exactly-sized output CSR, tombstones included."""
    table = DistributedHashTable(mesh8, ("d",), hash_range=1 << 12)
    rng = np.random.default_rng(29)
    state = _four_layer_state(table, rng)
    queries = jnp.asarray(rng.integers(0, 1 << 14, 256, dtype=np.uint32))
    res = table.retrieve(state, queries)  # planned caps (fused counts round)
    assert int(res.num_dropped) == 0
    want = np.asarray(table.query(state, queries))
    np.testing.assert_array_equal(np.asarray(res.counts), want)
    # out_capacity is the lane-rounded exact per-device maximum
    seg, out = table.plan_caps(state, queries)
    assert res.values.shape[0] // 8 == max(8, -(-out // 8) * 8)


def test_per_layer_counts_parity_and_collective_budget(mesh8):
    """retrieve(per_layer_counts=True): fused == legacy breakdown, row sums
    equal the merged counts, and the fused path STILL costs exactly 2
    all-to-alls — the breakdown rides the bitcast return buffer, not a
    second round (the ROADMAP "fused return payload packing" item)."""
    rng = np.random.default_rng(41)
    q = jnp.asarray(rng.integers(0, 1 << 14, 128, dtype=np.uint32))
    fused_t = DistributedHashTable(mesh8, ("d",), hash_range=1 << 12)
    legacy_t = DistributedHashTable(
        mesh8, ("d",), hash_range=1 << 12, fused_routing=False
    )
    state_f = _four_layer_state(fused_t, np.random.default_rng(41))
    state_l = _four_layer_state(legacy_t, np.random.default_rng(41))

    res_f = fused_t.retrieve(
        state_f, q, out_capacity=4096, seg_capacity=4096, per_layer_counts=True
    )
    res_l = legacy_t.retrieve(
        state_l, q, out_capacity=4096, seg_capacity=4096, per_layer_counts=True
    )
    assert res_f.layer_counts.shape == (128, 4)
    np.testing.assert_array_equal(
        np.asarray(res_f.layer_counts), np.asarray(res_l.layer_counts)
    )
    np.testing.assert_array_equal(
        np.asarray(res_f.layer_counts).sum(axis=1), np.asarray(res_f.counts)
    )
    # tombstoned rows contribute zero to their layer's column
    deleted = np.asarray(
        fused_t.query(state_f, q)
    )  # merged counts already exclude them
    np.testing.assert_array_equal(np.asarray(res_f.counts), deleted)

    # the provenance is layer-exact: a key inserted only in delta 2 shows
    # its count in column 2 and nowhere else
    fresh = jnp.asarray(rng.integers(1 << 15, 1 << 16, 16, dtype=np.uint32))
    s2 = fused_t.init(jnp.asarray(rng.integers(0, 1 << 14, 512, dtype=np.uint32)))
    s2 = s2.insert(jnp.asarray(rng.integers(0, 1 << 14, 64, dtype=np.uint32)))
    s2 = s2.insert(fresh)
    r2 = fused_t.retrieve(
        s2, jnp.concatenate([fresh, fresh]), out_capacity=512,
        seg_capacity=512, per_layer_counts=True,
    )
    lc = np.asarray(r2.layer_counts)
    assert (lc[:, 2] >= 1).all() and (lc[:, :2].sum() == 0)

    # collective budget unchanged: 2 (dispatch + fused ragged return)
    jx = jax.make_jaxpr(
        lambda s, qq: plans.exec_retrieve(
            fused_t, s, qq, out_capacity=2048, seg_capacity=2048,
            per_layer_counts=True,
        )
    )(state_f, q)
    assert count_primitive(jx.jaxpr, "all_to_all") == 2


def test_coherent_delta_geometry_is_small(mesh8):
    """Coherent deltas stride the base's bucket map: a small insert must not
    pay the base's O(hash_range / D) offsets array."""
    table = DistributedHashTable(mesh8, ("d",), hash_range=1 << 16)
    rng = np.random.default_rng(31)
    state = table.init(jnp.asarray(rng.integers(0, 1 << 16, 4096, dtype=np.uint32)))
    state = state.insert(jnp.asarray(rng.integers(0, 1 << 16, 64, dtype=np.uint32)))
    delta = state.deltas[0]
    assert delta.bucket_stride > 1
    assert delta.local_range_cap * 8 < state.base.local_range_cap
    # global offsets array: D * (local_range_cap + 2) rows
    assert delta.local.offsets.shape[0] < state.base.local.offsets.shape[0] // 8
