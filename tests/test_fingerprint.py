"""Fingerprint probe lane — engineered collisions, parity, fold, collectives.

The fingerprint-compressed probe path bisects a 1-lane uint32 fingerprint
array first and touches full key lanes only inside the matched fingerprint
run.  Its correctness story therefore rests on the *collision* case: two
distinct keys with equal fingerprints share a run, and the verification
bisection must separate them exactly — multiset counts, retrieved value
multisets, tombstone semantics all unchanged.

This suite manufactures real collisions instead of hoping for them: it
fingerprints a large random u64 candidate pool on device (the same
``fingerprint32`` the table uses) and mines birthday pairs with numpy.
One structural fact shapes the adversarial grid: every step of the murmur3
mix is invertible, so a message where only ONE 32-bit lane varies maps
that lane *bijectively* to the hash.  Consequences the tests encode:

* u32x1 — distinct 1-lane keys can never share a fingerprint; the
  fingerprint run degenerates to the equal-key multiplicity run, and the
  lane is pure overhead (which is why it defaults off for 1-lane keys).
* u64x2 — true fingerprint collisions exist only between keys differing
  in BOTH lanes (mined pairs); keys sharing the low or the high lane
  necessarily differ in fingerprint, so they instead stress the packed
  big-int compare inside a (fingerprint, key)-sorted bucket, where a
  single lane is all that separates them.

Grid: engineered collisions at multiplicity up to 1024, u32x1/u64x2 ×
mesh1/mesh8, fingerprint path vs forced-full-key path (byte-identical),
delete-then-reinsert across a ``fold_oldest`` boundary (epoch remap with
fingerprints present), and the fused-routing collective budget (exactly 2
all-to-alls per op) with the fingerprint lane on.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hashing, plans
from repro.core.maintenance import fold_oldest
from repro.core.schema import TableSchema, pack_u64
from repro.core.table import DistributedHashTable, retrieval_to_lists
from repro.obs.profiling import count_primitive
from test_table_state import _value_rows, _values_for

SCHEMAS = [
    pytest.param(TableSchema("uint32", 1), id="u32x1"),
    pytest.param(TableSchema("uint64", 2), id="u64x2"),
]


@functools.lru_cache(maxsize=None)
def _adversarial_pairs(key_dtype: str):
    """Three key pairs stressing the (fingerprint, key) probe layout.

    uint64: pair 0 is a *mined* true fingerprint collision — a 2^19
    random pool yields ~46 birthday pairs at 32-bit fingerprints,
    deterministic given the seed; both lanes differ (they must — the
    murmur mix is a bijection of any single varying lane).  Pair 1
    shares the low key lane, pair 2 the high lane: their fingerprints
    necessarily differ, so they exercise the packed compare that
    separates near-identical keys landing in one sorted bucket.

    uint32: the 1-lane fingerprint is a bijection of the key — distinct
    keys NEVER collide — so the pairs are plain distinct keys and the
    tests degenerate to multiplicity-run + parity coverage (the reason
    the lane defaults off for 1-lane schemas).
    """
    if key_dtype == "uint32":
        return ((0x0000BEEF, 0x0001BEEF), (3, 0x10003), (5, 0x20005))
    n = 1 << 19
    rng = np.random.default_rng(0xF1D0)
    raw = np.unique(rng.integers(0, 1 << 63, size=n, dtype=np.uint64))
    fp = np.asarray(hashing.fingerprint32(pack_u64(raw)))
    order = np.argsort(fp, kind="stable")
    fps = fp[order]
    dup = np.flatnonzero(fps[1:] == fps[:-1])
    assert len(dup) > 0, "collision mining failed — widen the pool"
    k1, k2 = sorted((int(raw[order[dup[0]]]), int(raw[order[dup[0] + 1]])))
    assert k1 != k2 and fp[order[dup[0]]] == fp[order[dup[0] + 1]]
    low_pair = (0x7_0000_1111, 0xBAD_0000_1111)  # equal low lane
    high_pair = (0x7777_0000_0000_0003, 0x7777_0000_0000_0009)  # equal high lane
    return ((k1, k2), low_pair, high_pair)


def _table(mesh, schema, fingerprint, **kw):
    # generous dispatch slack: a multiplicity-700 key routes every copy to
    # ONE owner shard (hot-key skew — see the ROADMAP replication item),
    # so per-shard capacity must cover the whole run, not the average
    return DistributedHashTable(
        mesh,
        ("d",),
        hash_range=1 << 12,
        schema=schema,
        fingerprint=fingerprint,
        capacity_slack=kw.pop("capacity_slack", 6.0),
        **kw,
    )


def _pack(schema, host_keys):
    return schema.pack_keys(np.asarray(host_keys, dtype=schema.key_dtype))


@pytest.mark.parametrize("schema", SCHEMAS)
@pytest.mark.parametrize("meshname", ["mesh1", "mesh8"])
def test_engineered_collisions_exact(schema, meshname, request):
    """Adversarial keys at multiplicity ≤1024: exact counts, exact value
    multisets (no cross-leak between fp-colliding keys), byte-identical
    to the forced full-key path."""
    mesh = request.getfixturevalue(meshname)
    (k1, k2), (la, lb), (ha, hb) = _adversarial_pairs(schema.key_dtype)
    rng = np.random.default_rng(3)

    # workload: k1 × 700 + k2 × 300 — for u64 one shared-fingerprint run
    # of 1000 (≤ 1024), whose verification pass must split 700/300 exactly
    # — plus the lane-sharing pairs and background noise.
    special = [(k1, 700), (k2, 300), (la, 17), (lb, 9), (ha, 5), (hb, 3)]
    lo, hi = (1 << 33, 1 << 34) if schema.key_dtype == "uint64" else (1 << 20, 1 << 31)
    # total padded to 2048 so the global array shards evenly on mesh8
    noise = rng.integers(lo, hi, size=2048 - sum(m for _, m in special)).astype(
        np.uint64
    )
    host = np.concatenate(
        [
            np.repeat(
                np.asarray([k for k, _ in special], np.uint64),
                [m for _, m in special],
            ),
            noise,
        ]
    ).astype(schema.key_dtype)
    values = _values_for(schema, 0, len(host))
    # shuffle so hot-key copies spread across *sender* shards — contiguous
    # runs overflow one sender's per-pair dispatch slot no matter the
    # owner-side slack (hot-key replication is a ROADMAP item)
    perm = np.random.default_rng(7).permutation(len(host))
    host, values = host[perm], values[perm]
    # oracle from the workload itself — robust to accidental aliasing
    expect = {}
    for k, v in zip(host.tolist(), _value_rows(values)):
        expect.setdefault(k, []).append(v)

    queries = np.asarray(
        [k1, k2, la, lb, ha, hb, k1 + 5, noise[0]], dtype=schema.key_dtype
    )
    want_counts = np.asarray(
        [len(expect.get(int(q), [])) for q in queries], np.int32
    )
    assert want_counts[0] == 700 and want_counts[1] == 300  # no aliasing

    res = {}
    for fp_on in (True, False):
        table = _table(mesh, schema, fp_on)
        state = table.init(_pack(schema, host), values=jnp.asarray(values))
        assert int(state.num_dropped) == 0, "dispatch capacity sizing bug"
        assert (table.use_fingerprint, state.base.local.fingerprints is not None) == (
            fp_on,
            fp_on,
        )
        counts = np.asarray(table.query(state, _pack(schema, queries)))
        np.testing.assert_array_equal(counts, want_counts)
        r = table.retrieve(state, _pack(schema, queries))
        assert int(r.num_dropped) == 0
        res[fp_on] = r
        per_q = retrieval_to_lists(r)
        for i, q in enumerate(queries.tolist()):
            got = sorted(_value_rows(np.asarray(per_q[i])))
            assert got == sorted(expect.get(int(q), [])), f"query {i}"

    for field in ("offsets", "counts", "values", "num_dropped"):
        np.testing.assert_array_equal(
            np.asarray(getattr(res[True], field)),
            np.asarray(getattr(res[False], field)),
            err_msg=f"fingerprint path diverged on {field}",
        )


@pytest.mark.parametrize("schema", SCHEMAS)
@pytest.mark.parametrize("meshname", ["mesh1", "mesh8"])
def test_collision_delete_reinsert_across_fold(schema, meshname, request):
    """Tombstone one colliding key, fold the epoch away, reinsert: the epoch
    remap must keep the surviving collision partner intact throughout."""
    mesh = request.getfixturevalue(meshname)
    (k1, k2), _, _ = _adversarial_pairs(schema.key_dtype)
    table = _table(mesh, schema, True, max_deltas=6)

    # all batch sizes are multiples of 8 so arrays shard evenly on mesh8
    base = np.repeat(np.asarray([k1, k2], np.uint64), [8, 8]).astype(schema.key_dtype)
    v0 = _values_for(schema, 0, 16)
    state = table.init(_pack(schema, base), values=jnp.asarray(v0))
    # delta 1: more of both colliding keys; delta 2: unrelated filler
    v1 = _values_for(schema, 100, 8)
    state = state.insert(
        _pack(schema, np.repeat(np.asarray([k1, k2], np.uint64), [4, 4]).astype(
            schema.key_dtype
        )),
        jnp.asarray(v1),
    )
    state = state.insert(
        _pack(schema, np.full(8, k1 + 7, schema.key_dtype)),
        jnp.asarray(_values_for(schema, 200, 8)),
    )
    # tombstone k1 everywhere (epoch 2), then fold the two oldest layers —
    # the tombstone epoch indices must remap with fingerprints present
    misses = np.asarray([k1 + i for i in range(100, 107)], schema.key_dtype)
    state = state.delete(
        _pack(schema, np.concatenate([[np.uint64(k1)], misses.astype(np.uint64)])
              .astype(schema.key_dtype))
    )
    folded = fold_oldest(state, 2)
    assert folded.base.local.fingerprints is not None

    q = _pack(
        schema,
        np.concatenate(
            [np.asarray([k1, k2, k1 + 7], np.uint64), misses[:5].astype(np.uint64)]
        ).astype(schema.key_dtype),
    )
    want0 = [0, 12, 8, 0, 0, 0, 0, 0]
    np.testing.assert_array_equal(np.asarray(table.query(folded, q)), want0)

    # reinsert k1 after the fold: fresh rows live, old rows stay dead
    v9 = _values_for(schema, 900, 8)
    refreshed = folded.insert(
        _pack(schema, np.full(8, k1, schema.key_dtype)), jnp.asarray(v9)
    )
    want1 = [8, 12, 8, 0, 0, 0, 0, 0]
    np.testing.assert_array_equal(np.asarray(table.query(refreshed, q)), want1)
    r = table.retrieve(refreshed, q)
    assert int(r.num_dropped) == 0
    per_q = retrieval_to_lists(r)
    assert sorted(_value_rows(np.asarray(per_q[0]))) == sorted(_value_rows(v9))
    assert sorted(_value_rows(np.asarray(per_q[1]))) == sorted(
        _value_rows(v0)[8:16] + _value_rows(v1)[4:8]
    )

    # full compact preserves the lane and the answers
    compacted = refreshed.compact()
    assert compacted.base.local.fingerprints is not None
    np.testing.assert_array_equal(np.asarray(table.query(compacted, q)), want1)


def test_fingerprint_default_by_schema(mesh1):
    """Auto default: multi-lane keys get the lane, 1-lane keys skip it;
    explicit override wins either way."""
    for schema, want in [(TableSchema("uint32", 1), False), (TableSchema("uint64", 1), True)]:
        t = DistributedHashTable(mesh1, ("d",), hash_range=256, schema=schema)
        assert t.use_fingerprint is want
        rng = np.random.default_rng(0)
        keys = _pack(schema, rng.integers(0, 1 << 16, 64).astype(schema.key_dtype))
        st = t.init(keys)
        assert (st.base.local.fingerprints is not None) is want
    t = DistributedHashTable(
        mesh1, ("d",), hash_range=256, schema=TableSchema("uint32", 1), fingerprint=True
    )
    assert t.use_fingerprint is True


def test_collective_budget_with_fingerprints(mesh8):
    """Fused 2-all-to-all budget holds with the fingerprint lane on: the
    routed fingerprints are derived owner-side, never exchanged."""
    schema = TableSchema("uint64", 2)
    table = DistributedHashTable(
        mesh8, ("d",), hash_range=1 << 12, schema=schema, max_deltas=8
    )
    assert table.use_fingerprint
    rng = np.random.default_rng(5)

    def keys(n):
        return _pack(schema, rng.integers(0, 1 << 40, n).astype(np.uint64))

    state = table.init(keys(512), values=jnp.asarray(_values_for(schema, 0, 512)))
    for _ in range(3):
        state = state.insert(keys(64), values=jnp.asarray(_values_for(schema, 0, 64)))
    state = state.delete(keys(16))
    assert state.base.local.fingerprints is not None

    q = keys(128)
    jx = jax.make_jaxpr(
        lambda s, qq: plans.exec_retrieve(
            table, s, qq, out_capacity=2048, seg_capacity=2048
        )
    )(state, q)
    assert count_primitive(jx.jaxpr, "all_to_all") == 2
    jq = jax.make_jaxpr(lambda s, qq: plans.exec_query(table, s, qq))(state, q)
    assert count_primitive(jq.jaxpr, "all_to_all") == 2
