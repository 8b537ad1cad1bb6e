"""KV-cache subsystem — upsert/TTL/eviction oracles, YCSB generator, hot keys.

The cache contract on top of the multiset core:

* **upsert** is read-your-writes and last-writer-wins: after
  ``upsert(state, keys, values)`` every key counts exactly 1 and
  retrieves exactly its newest value — across schema widths, mesh sizes,
  duplicate-heavy batches, and fold/compact boundaries.
* **TTL** expires *exactly* at the deadline epoch: a row put with
  ``ttl=T`` at clock ``t`` is visible through ``t+T-1`` and gone at
  ``t+T``, whichever side of a fold/compact the expiry is observed from.
* **Eviction reclaims capacity**: a steady upsert+expire stream through
  :class:`KVCache` holds both the live count and the allocated rows flat
  (the policy's expired-load escalation folds expired rows out of the
  base instead of growing it forever).
* Reads over TTL'd state stay on the **fused 2-all-to-all** plan (jaxpr
  asserted) — cache semantics never add collective rounds.
* **Hot-key replication** (``replicate_hot_keys``) spreads a zipfian
  hot key's rows across destination shards with zero dropped rows and
  exact merged counts at YCSB skew (theta = 0.99).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cache import KVCache, WORKLOADS, YCSBWorkload, ZipfianGenerator, key_of
from repro.core import maintenance, plans
from repro.core.maintenance import CompactionPolicy, fold_oldest
from repro.core.schema import TableSchema
from repro.core.table import DistributedHashTable, retrieval_to_lists
from repro.obs.profiling import count_primitive
from test_table_state import _keys_for, _value_rows, _values_for

SCHEMAS = [
    pytest.param(TableSchema("uint32", 1), id="u32x1"),
    pytest.param(TableSchema("uint64", 2), id="u64x2"),
]


def _table(mesh, d, schema=None, **kw):
    kw.setdefault("hash_range", 1 << 12)
    if schema is not None:
        kw["schema"] = schema
    return DistributedHashTable(mesh, ("d",), **kw)


def _values_of(table, state, queries):
    """Per-query value rows via retrieve (KV reads: at most one per key)."""
    q = table.schema.pack_keys(queries)
    res = table.retrieve(state, q, out_capacity=4096, seg_capacity=4096)
    assert int(res.num_dropped) == 0
    return [
        _value_rows(np.asarray(v)) for v in retrieval_to_lists(res)
    ]


# ---------------------------------------------------------------------------
# upsert: read-your-writes + last-writer-wins
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("schema", SCHEMAS)
@pytest.mark.parametrize("meshname", ["mesh1", "mesh8"])
def test_upsert_read_your_writes_last_writer_wins(schema, meshname, request):
    mesh = request.getfixturevalue(meshname)
    d = 8 if meshname == "mesh8" else 1
    table = _table(mesh, d, schema)
    rng = np.random.default_rng(17 + d + schema.value_cols)

    base_keys = _keys_for(schema, rng, 64)
    base_keys = np.unique(base_keys)
    state = table.init(
        table.schema.pack_keys(base_keys),
        jnp.asarray(_values_for(schema, 0, len(base_keys))),
    )

    # Overwrite half the existing keys + introduce fresh ones, with
    # in-batch duplicates: the LAST occurrence must win.
    old = base_keys[: len(base_keys) // 2]
    fresh = _keys_for(schema, rng, 16, lo=1 << 17, hi=1 << 18)
    fresh = np.unique(fresh)
    up_keys = np.concatenate([old, fresh, old])  # old repeated: dup batch
    up_vals = _values_for(schema, 10_000, len(up_keys))
    state = table.upsert(state, table.schema.pack_keys(up_keys), jnp.asarray(up_vals))

    queries = np.concatenate([base_keys, fresh])
    counts = np.asarray(table.query(state, table.schema.pack_keys(queries)))
    np.testing.assert_array_equal(counts, np.ones(len(queries), np.int32))

    expect = {}
    for k, v in zip(base_keys.tolist(), _value_rows(_values_for(schema, 0, len(base_keys)))):
        expect[int(k)] = v
    for k, v in zip(up_keys.tolist(), _value_rows(up_vals)):
        expect[int(k)] = v  # later occurrence overwrites: keep-last
    got = _values_of(table, state, queries)
    for k, vals in zip(queries.tolist(), got):
        assert vals == [expect[int(k)]], f"key {k}"

    # Read-your-writes composes: a second upsert over the same keys wins
    # again, and the result survives a fold and a full compact unchanged.
    up2_vals = _values_for(schema, 50_000, len(queries))
    state = table.upsert(state, table.schema.pack_keys(queries), jnp.asarray(up2_vals))
    want2 = [[v] for v in _value_rows(up2_vals)]
    for st in (state, fold_oldest(state, 1), state.compact()):
        counts = np.asarray(table.query(st, table.schema.pack_keys(queries)))
        np.testing.assert_array_equal(counts, np.ones(len(queries), np.int32))
        assert _values_of(table, st, queries) == want2


# ---------------------------------------------------------------------------
# TTL: expiry exactly at the deadline epoch, across fold boundaries
# ---------------------------------------------------------------------------
def test_ttl_expires_exactly_at_boundary(mesh8):
    table = _table(mesh8, 8)
    keys = np.arange(1, 33, dtype=np.uint32)
    state = table.init(jnp.asarray(keys), jnp.asarray(np.arange(32, dtype=np.int32)))

    ttl_keys = keys[:8]
    state = table.upsert(
        state, jnp.asarray(ttl_keys), jnp.asarray(np.arange(8, dtype=np.int32)), ttl=5
    )
    q = jnp.asarray(keys)
    for now in (0, 4):  # visible strictly before the deadline
        counts = np.asarray(table.query(state.advance(now), q))
        np.testing.assert_array_equal(counts, np.ones(32, np.int32))
    for now in (5, 9):  # gone exactly at (and after) the deadline
        counts = np.asarray(table.query(state.advance(now), q))
        want = np.ones(32, np.int32)
        want[:8] = 0
        np.testing.assert_array_equal(counts, want)
    # the clock is data, not structure: advancing must not retrace
    jx = jax.make_jaxpr(lambda s, qq: plans.exec_query(table, s, qq))(state, q)
    assert count_primitive(jx.jaxpr, "all_to_all") == 2


def test_delete_upsert_expire_across_fold_boundary(mesh8):
    """delete -> upsert(ttl) -> fold_oldest straddling the tombstones."""
    table = _table(mesh8, 8)
    keys = np.arange(1, 65, dtype=np.uint32)
    state = table.init(jnp.asarray(keys), jnp.asarray(np.arange(64, dtype=np.int32)))

    victim = keys[:8]
    state = table.delete(state, jnp.asarray(victim))
    state = table.upsert(
        state,
        jnp.asarray(victim),
        jnp.asarray(np.arange(100, 108, dtype=np.int32)),
        ttl=3,
    )
    # pad the ring so a fold of 2 straddles the delete+upsert epochs
    filler = np.arange(1 << 10, (1 << 10) + 16, dtype=np.uint32)
    state = state.insert(jnp.asarray(filler), jnp.asarray(np.arange(16, dtype=np.int32)))

    q = jnp.asarray(victim)
    variants = {
        "unfolded": state,
        "fold1": fold_oldest(state, 1),
        "fold2": fold_oldest(state, 2),
        "compact": state.compact(),
    }
    for name, st in variants.items():
        alive = np.asarray(table.query(st.advance(2), q))
        np.testing.assert_array_equal(
            alive, np.ones(8, np.int32), err_msg=f"{name}: visible before expiry"
        )
        dead = np.asarray(table.query(st.advance(3), q))
        np.testing.assert_array_equal(
            dead, np.zeros(8, np.int32), err_msg=f"{name}: gone at the deadline"
        )
        vals = _values_of(table, st.advance(2), victim)
        assert vals == [[100 + i] for i in range(8)], name


# ---------------------------------------------------------------------------
# eviction: a steady upsert+expire stream holds capacity flat
# ---------------------------------------------------------------------------
def test_eviction_reclaims_capacity(mesh8):
    table = _table(mesh8, 8, max_deltas=4, tombstone_capacity=512)
    cache = KVCache(table, default_ttl=2)
    keys = np.arange(1, 65, dtype=np.uint32)

    allocs = []
    for t in range(12):
        cache.put(keys, np.full(64, t, np.int32))
        cache.tick()
        st = cache.stats()
        allocs.append(st.base_rows + st.delta_rows)
        # live rows never exceed the working set (every key has exactly
        # one unexpired version; expired versions are masked)
        assert cache.live_count() == 64

    assert cache.evictions >= 1, "expired-load trigger never escalated"
    # Allocation is flat, not monotone: the second half of the stream must
    # not grow past the high-water mark of the first half (eviction
    # actually returns capacity).
    assert max(allocs[6:]) <= max(allocs[:6]), allocs
    # values are the newest generation everywhere
    got = cache.get(keys)
    np.testing.assert_array_equal(got, np.full(64, 11, np.int32))
    # and a forced eviction on a fully-expired cache empties it
    cache.advance(cache.now + 2)
    assert cache.live_count() == 0
    cache.evict_expired()
    assert cache.stats().tombstone_count == 0
    assert cache.get(keys)[0] == -1


def test_kvcache_get_contains_delete(mesh8):
    table = _table(mesh8, 8, max_deltas=4, tombstone_capacity=256)
    cache = KVCache(table)
    keys = np.arange(10, 20, dtype=np.uint32)
    cache.put(keys, np.arange(10, dtype=np.int32) * 3)
    assert cache.contains(keys).all()
    np.testing.assert_array_equal(cache.get(keys), np.arange(10, dtype=np.int32) * 3)
    cache.delete(keys[:5])
    assert not cache.contains(keys[:5]).any()
    assert cache.contains(keys[5:]).all()
    np.testing.assert_array_equal(cache.get(keys[5:]), np.arange(5, 10, dtype=np.int32) * 3)
    # ragged (non-device-multiple) reads pad internally
    assert cache.get(keys[5:8]).shape == (3,)


# ---------------------------------------------------------------------------
# stats-driven folds: the cold prefix folds first
# ---------------------------------------------------------------------------
def test_stats_driven_fold_amount_cold_prefix(mesh8):
    table = _table(mesh8, 8, max_deltas=6, tombstone_capacity=512)
    keys = np.arange(1, 257, dtype=np.uint32)
    state = table.init(jnp.asarray(keys), jnp.asarray(np.arange(256, dtype=np.int32)))

    # two cold deltas (fully deleted), then one hot delta (all live)
    cold1 = np.arange(1 << 10, (1 << 10) + 32, dtype=np.uint32)
    cold2 = np.arange(1 << 11, (1 << 11) + 32, dtype=np.uint32)
    hot = np.arange(1 << 12, (1 << 12) + 32, dtype=np.uint32)
    for batch in (cold1, cold2, hot):
        state = state.insert(jnp.asarray(batch), jnp.asarray(np.arange(32, dtype=np.int32)))
    state = table.delete(state, jnp.asarray(np.concatenate([cold1, cold2])))

    layer_live = maintenance.collect_layer_live(state)
    assert len(layer_live) == 4  # base + 3 deltas
    assert layer_live[1][0] == 0 and layer_live[2][0] == 0  # cold deltas
    assert layer_live[3][0] == 32  # hot delta

    policy = CompactionPolicy(fold_k=None, cold_live_ratio=0.5)
    stats = state.stats()
    k = policy.fold_amount(stats, layer_live)
    assert k == 2  # exactly the cold prefix, stopping before the hot layer

    folded = fold_oldest(state, k)
    assert len(folded.deltas) == 1
    counts = np.asarray(table.query(folded, jnp.asarray(hot)))
    np.testing.assert_array_equal(counts, np.ones(32, np.int32))
    counts = np.asarray(table.query(folded, jnp.asarray(cold1)))
    np.testing.assert_array_equal(counts, np.zeros(32, np.int32))

    # static override still wins
    assert CompactionPolicy(fold_k=3).fold_amount(stats, layer_live) == 3


# ---------------------------------------------------------------------------
# hot-key replication at YCSB skew
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("theta", [0.99, 1.2])
def test_hot_key_replication_zipf(mesh8, theta):
    """theta >= 0.99 zipfian insert: zero drops, exact merged counts."""
    table = _table(mesh8, 8, capacity_slack=2.0, replicate_hot_keys=4)
    base = np.arange(1, 257, dtype=np.uint32)
    state = table.init(jnp.asarray(base), jnp.asarray(np.arange(256, dtype=np.int32)))

    zipf = ZipfianGenerator(64, theta=theta, seed=5)
    ranks = zipf.sample(512)
    # distinct key ids, disjoint from the base population
    batch = (ranks + 1).astype(np.uint32) * np.uint32(3) + np.uint32(1 << 14)
    state = state.insert(
        jnp.asarray(batch), jnp.asarray(np.arange(512, dtype=np.int32))
    )

    assert table.skew_fallbacks == 0, "replication should absorb the skew"
    assert table.hot_keys, "the zipf head never went hot"
    assert int(state.num_dropped) == 0

    uniq, want = np.unique(batch, return_counts=True)
    pad = (-len(uniq)) % 8  # queries ship device-aligned; EMPTY counts 0
    q = np.concatenate([uniq, np.full(pad, 0xFFFFFFFF, np.uint32)])
    counts = np.asarray(table.query(state, jnp.asarray(q)))[: len(uniq)]
    np.testing.assert_array_equal(counts, want.astype(np.int32))
    # non-hot base keys are unaffected (count once, not per-replica-round)
    others = base[200:232]
    counts = np.asarray(table.query(state, jnp.asarray(others)))
    np.testing.assert_array_equal(counts, np.ones(32, np.int32))


# ---------------------------------------------------------------------------
# YCSB workload generator
# ---------------------------------------------------------------------------
def test_key_of_is_injective_and_never_empty():
    k = key_of(np.arange(1 << 16))
    assert len(np.unique(k)) == 1 << 16
    assert not np.any(k == np.uint32(0xFFFFFFFF))


def test_zipfian_is_skewed_and_bounded():
    z = ZipfianGenerator(1000, theta=0.99, seed=0)
    s = z.sample(20_000)
    assert s.min() >= 0 and s.max() < 1000
    # zipf(0.99, 1000): the head rank draws ~13% of all samples
    head = np.mean(s == 0)
    assert 0.08 < head < 0.20, head
    # determinism under the same seed
    np.testing.assert_array_equal(
        ZipfianGenerator(1000, theta=0.99, seed=3).sample(64),
        ZipfianGenerator(1000, theta=0.99, seed=3).sample(64),
    )


@pytest.mark.parametrize("letter", list("ABCDEF"))
def test_workload_mix_and_shapes(letter):
    w = YCSBWorkload(WORKLOADS[letter], 512, batch=128, scan_len=4, seed=11)
    spec = WORKLOADS[letter]
    tot = {k: 0 for k in ("read", "update", "insert", "scan", "rmw")}
    for kind, keys, vals in w.batches(2000):
        n = keys.shape[0] // (w.scan_len if kind == "scan" else 1)
        tot[kind] += n
        if kind in ("update", "insert", "rmw"):
            assert vals is not None and vals.shape[0] == keys.shape[0]
        else:
            assert vals is None
        assert keys.dtype == np.uint32
    assert sum(tot.values()) == 2000
    for name, frac in (("read", spec.read), ("update", spec.update),
                       ("insert", spec.insert), ("scan", spec.scan),
                       ("rmw", spec.rmw)):
        assert abs(tot[name] / 2000 - frac) < 0.05, (letter, name, tot)
    # insert-bearing workloads advance the cursor; their keys are fresh
    if spec.insert:
        assert w.inserted == 512 + tot["insert"]


def test_workload_drives_kvcache_exactly(mesh8):
    """A zipfian A-mix applied through KVCache matches a dict oracle."""
    table = _table(mesh8, 8, max_deltas=4, tombstone_capacity=512)
    w = YCSBWorkload(WORKLOADS["A"], 128, batch=64, seed=2)
    cache = KVCache(table, w.load_keys(), w.load_values().astype(np.int32))
    oracle = dict(zip(w.load_keys().tolist(), w.load_values().tolist()))

    for kind, keys, vals in w.batches(512):
        if kind == "read":
            got = cache.get(keys)
            want = np.array([oracle.get(int(k), -1) for k in keys], np.int32)
            np.testing.assert_array_equal(got, want)
        else:  # update
            cache.put(keys, vals)
            for k, v in zip(keys.tolist(), vals.tolist()):
                oracle[int(k)] = v
    assert cache.live_count() == len(oracle)


# ---------------------------------------------------------------------------
# server integration: submit_upsert + advance
# ---------------------------------------------------------------------------
def test_server_upsert_and_clock(mesh8):
    from repro.serve_table import CompactionPolicy as SP
    from repro.serve_table import MicroBatcher, TableServer

    table = _table(mesh8, 8, max_deltas=4, tombstone_capacity=256)
    n = 128
    server = TableServer(
        table,
        np.arange(1, n + 1, dtype=np.uint32),
        np.arange(n, dtype=np.int32),
        policy=SP(max_delta_depth=2, fold_k=1, tombstone_load=0.9),
        batcher=MicroBatcher(table, min_bucket=16),
        write_bucket=16,
    )
    keys = np.arange(1, 17, dtype=np.uint32)
    # duplicate submissions dedup keep-last at admission
    server.submit_upsert(
        np.concatenate([keys, keys]),
        np.concatenate([np.zeros(16, np.int32), np.arange(16, dtype=np.int32) + 500]),
        ttl=4,
    )
    server.drain()
    counts, _ = server.query_many([keys])
    np.testing.assert_array_equal(counts[0], np.ones(16, np.int32))
    (vals,), _ = server.retrieve_many([keys])
    assert [int(v[0]) for v in vals] == [500 + i for i in range(16)]

    server.advance(3)
    counts, _ = server.query_many([keys])
    np.testing.assert_array_equal(counts[0], np.ones(16, np.int32))
    server.advance(4)  # the TTL deadline: rows age out of the snapshot
    counts, _ = server.query_many([keys])
    np.testing.assert_array_equal(counts[0], np.zeros(16, np.int32))
    # untouched keys still live
    rest = np.arange(17, 33, dtype=np.uint32)
    counts, _ = server.query_many([rest])
    np.testing.assert_array_equal(counts[0], np.ones(16, np.int32))
    assert server.stats().last_error is None
