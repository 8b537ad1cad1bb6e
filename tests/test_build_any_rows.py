"""A build of any row count, and the exchange's row counts.

``DistributedHashTable.init`` pads a row count that is not a multiple of
the devices with ``EMPTY_KEY`` sentinels: the answers must be the NumPy
reference's and the one-device table's, and no padding row may show up in
a result, a live count or a drop count.  Retrieve and join results carry
the real rows of their two all-to-alls (``exchange_rows``): the probe keys
routed and the result rows returned, never more than the slots shipped.
"""
import jax
import numpy as np
import pytest

from repro.core import multi_hashgraph, plans
from repro.core.hashgraph import EMPTY_KEY
from repro.core.schema import TableSchema
from repro.core.table import (
    DistributedHashTable,
    join_to_pairs,
    retrieval_to_lists,
    table_mesh,
)
from repro.obs.tracing import process_tracer

CAP = 4096


def _data(n, seed):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, n // 3 + 1, n).astype(np.uint32)
    values = rng.integers(0, 1 << 20, (n, 2)).astype(np.int32)
    return keys, values


def _queries(keys, ndev, seed):
    """Present keys, absent keys and the sentinel itself, a multiple of
    ``ndev`` long."""
    rng = np.random.default_rng(seed + 1)
    present = rng.choice(keys, 40)
    absent = np.arange(5, dtype=np.uint32) + np.uint32(1 << 30)
    q = np.concatenate([present, absent, [np.uint32(EMPTY_KEY)] * 3]).astype(np.uint32)
    return q[: (q.shape[0] // ndev) * ndev]


def _table(ndev, n, **kw):
    return DistributedHashTable(
        table_mesh(jax.devices()[:ndev]), ("d",), hash_range=n,
        schema=TableSchema("uint32", 2), **kw,
    )


def _reference_rows(keys, values, q):
    """Sorted (query index, value columns) rows of the inner join."""
    rows = [
        (i, *values[j]) for i, k in enumerate(q) for j in np.nonzero(keys == k)[0]
    ]
    return np.array(sorted(rows), np.int64).reshape(-1, 3)


def _answers(ndev, keys, values, q):
    table = _table(ndev, keys.shape[0])
    state = table.init(keys, values)
    counts = np.asarray(table.query(state, q))
    retrieved = retrieval_to_lists(
        table.plan_retrieve(num_queries=q.shape[0], out_capacity=CAP, seg_capacity=CAP)(
            state, q
        )
    )
    joined = table.plan_join(num_queries=q.shape[0], out_capacity=CAP, seg_capacity=CAP)(
        state, q
    )
    pairs = join_to_pairs(joined)
    assert int(joined.num_dropped) == 0
    return {
        "counts": counts,
        "retrieved": [np.array(sorted(map(tuple, r)), np.int64).reshape(-1, 2) for r in retrieved],
        "joined": np.array(sorted(map(tuple, pairs)), np.int64).reshape(-1, 3),
        "live": int(plans.exec_live_count(table, state)),
        "dropped": int(state.base.num_dropped),
    }


@pytest.mark.parametrize("ndev", [4, 8])
@pytest.mark.parametrize("rest", [1, 2, 3])
def test_any_row_count_answers_as_the_reference(ndev, rest):
    n = 97 * ndev + rest
    keys, values = _data(n, seed=ndev * 10 + rest)
    q = _queries(keys, ndev, seed=rest)
    got = _answers(ndev, keys, values, q)
    want = _reference_rows(keys, values, q)

    np.testing.assert_array_equal(got["counts"], [(keys == k).sum() for k in q])
    for i, rows in enumerate(got["retrieved"]):
        np.testing.assert_array_equal(rows, want[want[:, 0] == i][:, 1:])
    np.testing.assert_array_equal(got["joined"], want)
    assert not (got["joined"][:, 1:] == -1).any()  # no padding row's values
    assert got["live"] == n and got["dropped"] == 0

    one = _answers(1, keys, values, q)
    np.testing.assert_array_equal(got["counts"], one["counts"])
    np.testing.assert_array_equal(got["joined"], one["joined"])
    for a, b in zip(got["retrieved"], one["retrieved"]):
        np.testing.assert_array_equal(a, b)


def test_default_row_ids_skip_the_padding():
    """With no values the payload is the global row id: rows 0..N-1 keep
    theirs, and no key answers with a padding row's id."""
    n = 4 * 50 + 3
    keys = np.arange(n, dtype=np.uint32) * 7 + 1
    table = DistributedHashTable(table_mesh(jax.devices()[:4]), ("d",), hash_range=n)
    state = table.init(keys)
    plan = table.plan_join(num_queries=n + 1, out_capacity=CAP, seg_capacity=CAP)
    q = np.concatenate([keys, [np.uint32(EMPTY_KEY)]])
    pairs = join_to_pairs(plan(state, q))
    np.testing.assert_array_equal(pairs[np.argsort(pairs[:, 0])], np.stack([np.arange(n)] * 2, 1))


def _slots_counter():
    snap = process_tracer().registry.snapshot()
    return np.array(
        [snap.value("exchange_slots_total", {"phase": p}) for p in multi_hashgraph.EXCHANGE_PHASES]
    )


@pytest.mark.parametrize("ndev", [4, 8])
@pytest.mark.parametrize("kind", ["join", "retrieve"])
@pytest.mark.parametrize("fused", [True, False])
def test_exchange_rows_count_what_was_routed(ndev, kind, fused):
    n = 64 * ndev + 1
    keys, values = _data(n, seed=ndev)
    table = _table(ndev, n, fused_routing=None if fused else False)
    state = table.init(keys, values)
    extra_keys, extra_values = _data(8 * ndev, seed=ndev + 1)
    state = table.insert(state, extra_keys, extra_values)  # a second layer
    q = _queries(keys, ndev, seed=ndev)
    make = table.plan_join if kind == "join" else table.plan_retrieve
    plan = make(num_queries=q.shape[0], out_capacity=CAP, seg_capacity=CAP)

    before = _slots_counter()
    res = plan(state, q)
    slots = _slots_counter() - before
    rows = np.asarray(res.exchange_rows)

    real = int((q != np.uint32(EMPTY_KEY)).sum())
    matched = int(sum(((keys == k).sum() + (extra_keys == k).sum()) for k in q if k != EMPTY_KEY))
    rounds = 1 if fused else 2
    assert rows.shape == (ndev, 2)
    np.testing.assert_array_equal(rows.sum(axis=0), [rounds * real, matched])
    expected_slots = multi_hashgraph.exchange_slots(
        q.shape[0], ndev, table.capacity_slack, CAP, rounds
    )
    np.testing.assert_array_equal(slots, expected_slots)
    assert (rows <= np.array(expected_slots) // ndev).all()
