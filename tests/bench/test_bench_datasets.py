"""Each configuration's data generator against its plain reference, tiny."""
import numpy as np
import pytest

from bench.datasets.kv_records import KVRecords
from bench.datasets.tpch_lineitem import LINES_MAX, LineitemShare
from bench.mix import affine_perm, mix64, unmix64

SEEDS = [0, 7, 2**31 + 11, 2**40 + 3]


def test_mix64_is_a_bijection_with_its_inverse():
    x = np.array([0, 1, 2**63, 2**64 - 1, 12345678901234], np.uint64)
    assert np.array_equal(unmix64(mix64(x)), x)
    y = np.arange(1 << 16, dtype=np.uint64)
    assert np.unique(mix64(y)).size == y.size


@pytest.mark.parametrize("n", [7, 1 << 12, 150_000_000])
def test_affine_perm_permutes(n):
    a, b = affine_perm(n, 5, 1)
    i = np.arange(min(n, 1 << 16), dtype=np.int64)
    j = (i * a + b) % n
    assert np.unique(j).size == i.size
    if n < 1 << 16:
        assert set(j.tolist()) == set(range(n))


@pytest.mark.parametrize("seed", SEEDS)
def test_kv_records_against_reference(seed):
    data = KVRecords(1 << 12, 6, seed)
    keys, values = data.table()
    assert keys.dtype == np.uint64 and values.shape == (1 << 12, 6)
    assert np.unique(keys).size == keys.size
    assert np.all(data.count(keys) == 1)
    misses = data.key_of(np.arange(1 << 12, 1 << 14, dtype=np.uint64))
    assert np.all(data.count(misses) == 0)
    pick = np.array([0, 5, 4095])
    assert np.array_equal(data.values_of(keys[pick]), values[pick])


def test_kv_records_differ_by_seed():
    a, _ = KVRecords(256, 6, 1).table()
    b, _ = KVRecords(256, 6, 2).table()
    assert not np.intersect1d(a, b).size


@pytest.mark.parametrize("seed", SEEDS)
def test_lineitem_share_against_reference(seed):
    data = LineitemShare(160_000, 16, seed)
    keys, values = data.table()
    assert keys.shape[0] == data.rows == values.shape[0]
    order_keys, counts = np.unique(keys, return_counts=True)
    assert order_keys.size == data.orders_here
    assert np.array_equal(data.lines_of(order_keys), counts)
    assert counts.min() == 1 and counts.max() == LINES_MAX
    assert np.all((order_keys - 1) % 32 < 8) and order_keys.max() < 600_000_000
    # every lineitem the reference gives is a row of the table, and back
    probe = order_keys[:: max(1, order_keys.size // 97)]
    pos, want = data.rows_of(probe)
    sel = np.isin(keys, probe)
    table_rows = np.concatenate([keys[sel, None].astype(np.int64), values[sel]], axis=1)
    ref_rows = np.concatenate([probe[pos, None].astype(np.int64), want], axis=1)
    assert np.array_equal(np.unique(table_rows, axis=0), np.unique(ref_rows, axis=0))
    assert table_rows.shape == ref_rows.shape
    qty, price, discount, ship = values.T
    assert qty.min() >= 1 and qty.max() <= 50
    assert np.all(price % qty == 0)
    assert (price // qty).min() >= 90_000 and (price // qty).max() <= 209_999
    assert discount.max() <= 1000 and np.all(discount % 100 == 0)
    assert ship.min() >= 8036 and ship.max() <= 10_561  # 1992-01-02 .. 1998-12-01


def test_lineitem_rows_fixed_across_seeds_and_misses_count_zero():
    rows = {LineitemShare(160_000, 16, s).rows for s in SEEDS}
    assert len(rows) == 1
    data = LineitemShare(160_000, 16, 3)
    held = set(np.unique(data.table()[0]).tolist())
    other = np.array([k for k in range(1, 5000) if k not in held], np.uint32)
    assert np.all(data.lines_of(other) == 0)


def test_morsels_cover_every_order_once_per_pass():
    data = LineitemShare(160_000, 16, 9)
    size = 1024
    n = -(-data.orders_here // size)
    keys = np.concatenate([data.morsel(m, size) for m in range(n)])[: data.orders_here]
    assert np.unique(keys).size == data.orders_here
    assert np.all(data.lines_of(keys) >= 1)


def test_tables_made_in_chunks_equal_the_reference_rows():
    """Sizes just past one chunk, so the chunk edges are checked."""
    kv = KVRecords((1 << 22) + 6, 6, 5)
    keys, values = kv.table()
    idx = np.arange(keys.shape[0], dtype=np.uint64)
    assert np.array_equal(keys, kv.key_of(idx))
    assert np.array_equal(values, kv.values_of(keys))

    share = LineitemShare(16 * ((1 << 17) * LINES_MAX + 3), 16, 5)
    keys, values = share.table()
    order_keys = share.orderkey(np.arange(share.orders_here))
    pos, want = share.rows_of(order_keys)
    assert np.array_equal(keys, order_keys[pos])
    assert np.array_equal(values, want)
