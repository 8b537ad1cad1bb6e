"""``hashgraph.csr_gather`` against a plain NumPy concatenation of the runs.

The output slots are expanded by a scatter of per-row marks and a prefix
sum; these cases pin every output of the contract
``(offsets, row_idx, gathered, num_dropped)`` exactly, on the shapes where
that expansion could go wrong: zero-count rows (leading, inner, trailing,
all), a single row, totals at and past capacity, and fewer slots than rows.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hashgraph
from repro.kernels.ref import csr_gather_ref

CASES = {
    "inner_zero_rows": ([2, 0, 3, 0, 0, 1], 12),
    "leading_and_trailing_zero_rows": ([0, 0, 2, 3, 0, 0], 8),
    "all_rows_zero": ([0, 0, 0, 0], 5),
    "single_row": ([5], 7),
    "single_zero_row": ([0], 3),
    "total_equals_capacity": ([1, 4, 0, 2], 7),
    "total_past_capacity": ([3, 0, 4, 5, 0], 6),
    "total_past_capacity_at_a_run_start": ([3, 0, 4, 5], 7),
    "capacity_below_rows": ([1, 2, 0, 1, 3, 1, 1, 2, 0, 1], 3),
    "one_slot": ([0, 2, 1], 1),
}
PAYLOADS = {"column": (None, -1), "rows_custom_fill": (3, 7)}


def _numpy_csr(starts, counts, table, capacity, fill):
    """The contract's four outputs, from the plain reference of the runs."""
    values, rows = csr_gather_ref(starts, counts, table, capacity, fill=fill)
    offsets = np.concatenate([[0], np.cumsum(counts)])
    dropped = max(0, int(offsets[-1]) - capacity)
    return np.minimum(offsets, capacity), np.asarray(rows), np.asarray(values), dropped


def _inputs(counts, cols, seed):
    rng = np.random.default_rng(seed)
    counts = np.asarray(counts, np.int32)
    tn = int(counts.sum()) + 16
    shape = (tn,) if cols is None else (tn, cols)
    table = rng.integers(-1000, 1000, size=shape).astype(np.int32)
    starts = np.array([rng.integers(0, tn - c + 1) for c in counts], np.int32)
    return starts, counts, table


def _assert_contract(got, want):
    offsets, rows, values, dropped = got
    np.testing.assert_array_equal(np.asarray(offsets), want[0])
    np.testing.assert_array_equal(np.asarray(rows), want[1])
    np.testing.assert_array_equal(np.asarray(values), want[2])
    assert int(dropped) == want[3]
    assert offsets.dtype == rows.dtype == dropped.dtype == jnp.int32


@pytest.mark.parametrize("payload", PAYLOADS)
@pytest.mark.parametrize("case", CASES)
def test_csr_gather_matches_numpy(case, payload):
    counts, capacity = CASES[case]
    cols, fill = PAYLOADS[payload]
    starts, counts, table = _inputs(counts, cols, seed=len(case))
    want = _numpy_csr(starts, counts, table, capacity, fill)
    got = hashgraph.csr_gather(
        jnp.asarray(starts), jnp.asarray(counts), jnp.asarray(table), capacity,
        fill=jnp.int32(fill),
    )
    _assert_contract(got, want)
    jitted = jax.jit(
        lambda s, c, t: hashgraph.csr_gather(s, c, t, capacity, fill=jnp.int32(fill))
    )(jnp.asarray(starts), jnp.asarray(counts), jnp.asarray(table))
    _assert_contract(jitted, want)


@pytest.mark.parametrize("seed", range(4))
def test_csr_gather_random_ragged_matches_numpy(seed):
    rng = np.random.default_rng(100 + seed)
    n = int(rng.integers(1, 300))
    counts = rng.integers(0, 9, n) * (rng.random(n) < 0.7)
    capacity = int(rng.integers(1, 2 * counts.sum() + 2))
    starts, counts, table = _inputs(counts, 2, seed)
    got = hashgraph.csr_gather(
        jnp.asarray(starts), jnp.asarray(counts), jnp.asarray(table), capacity
    )
    _assert_contract(got, _numpy_csr(starts, counts, table, capacity, -1))


def test_csr_gather_vmapped_over_sources_matches_numpy():
    """The owner's use: one vmapped gather per source block over a shared
    table, each block with its own offsets, capacity report and zero rows."""
    rng = np.random.default_rng(7)
    n, capacity = 64, 96
    counts = rng.integers(0, 4, (4, n)) * (rng.random((4, n)) < 0.6)
    counts[1] = 0  # a source that sent nothing
    counts[2, -8:] = 40  # one that overflows its segment
    table = rng.integers(-1000, 1000, size=(4096, 4)).astype(np.int32)
    starts = rng.integers(0, 4096 - 40, (4, n)).astype(np.int32)
    got = jax.vmap(lambda s, c: hashgraph.csr_gather(s, c, jnp.asarray(table), capacity))(
        jnp.asarray(starts), jnp.asarray(counts.astype(np.int32))
    )
    for b in range(4):
        want = _numpy_csr(starts[b], counts[b], table, capacity, -1)
        _assert_contract(tuple(x[b] for x in got), want)
    assert int(got[3][2]) > 0 and int(got[3][1]) == 0
