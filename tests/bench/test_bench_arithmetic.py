"""The benchmark's metric arithmetic: percentiles over every request, rates
over the whole window, the roofline's byte count, arrivals."""
import json
from types import SimpleNamespace

import numpy as np
import pytest

from bench.arrivals import ZipfianGenerator, poisson_arrivals
from bench.roofline import PEAKS_FILE, peaks_for, probe_min_bytes


def _driver(name, config=None):
    from bench.harness import Benchmark

    cell = SimpleNamespace(config=config or {}, traffic={})
    return Benchmark().driver(name).Driver(cell)


@pytest.mark.parametrize("missing", [0, 1, 3])
def test_latency_percentiles_count_every_request(missing):
    # 100 requests, the slowest 2 far out; a request with no answer counts
    # as having waited until the wait ended, 90 s after its arrival
    d = _driver("frontend_open_loop")
    d.due = np.arange(100, dtype=np.float64)
    d.submitted = d.due + 0.001
    d.done = d.due + np.concatenate([np.full(98, 1.0), [50.0, 60.0]])
    d.answers = [np.ones(1)] * 100
    for i in range(missing):
        d.done[i] = np.nan
        d.answers[i] = None
    d.wait_end = d.due[-1] + 90.0
    lat = np.where(np.isnan(d.done), d.wait_end, d.done) - d.due
    got = d.latency()
    assert got["get_p50_ms"] == pytest.approx(np.percentile(lat, 50) * 1e3)
    assert got["get_p99_ms"] == pytest.approx(np.percentile(lat, 99) * 1e3)
    assert got["requests"] == 100
    if missing == 0:
        assert got["get_p99_ms"] == pytest.approx((50.0 + 0.01 * 10.0) * 1e3)
    else:
        assert got["get_p99_ms"] > 60e3  # the missing requests lie beyond every answer


def test_probe_rate_is_over_the_whole_window():
    # 10 calls of 2^17 keys in a 2.5 s window, one of them incomplete
    d = _driver("join_closed_loop", {"key_dtype": "uint32", "value_cols": 4})
    d.morsel_keys, d.n_morsels, d.spans = 1 << 17, 3, {}
    d.totals = [(None, 0)] * 10
    d.expected_rows = [4 << 17] * 3
    d.complete_calls, d.window_s = 9, 2.5
    out = d.outcome()
    assert out.end_to_end == {"probe_keys_per_s": 9 * (1 << 17) / 2.5}
    assert out.attempted == 10 and out.failed == 1


def test_probe_min_bytes_counts_required_traffic_only():
    # 2^20 probes, 4 rows each: keys + directory read once, rows read once,
    # output rows (index + payload) written once
    m, rows = 1 << 20, 4 << 20
    got = probe_min_bytes(m, rows, key_bytes=4, value_bytes=16)
    assert got == m * (4 + 8) + rows * (4 + 16) + rows * (4 + 16)
    assert 170e6 < got < 190e6
    assert probe_min_bytes(m, 0, key_bytes=8, value_bytes=24) == m * 16


def test_peaks_table_has_v5e_and_refuses_others():
    p = peaks_for("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12 and p["hbm_bytes"] == 16e9
    assert "source" in json.loads(PEAKS_FILE.read_text())
    with pytest.raises(KeyError):
        peaks_for("TPU v4")
    with pytest.raises(KeyError):
        peaks_for("cpu")


def test_arrivals_are_the_same_work_for_every_seed():
    a = poisson_arrivals(10_000, 10.0, np.random.default_rng(1))
    b = poisson_arrivals(10_000, 10.0, np.random.default_rng(2))
    assert a[-1] == pytest.approx(10.0) and b[-1] == pytest.approx(10.0)
    gaps, gaps_b = np.diff(a, prepend=0.0), np.diff(b, prepend=0.0)
    assert np.all(gaps > 0)
    assert np.allclose(np.sort(gaps), np.sort(gaps_b), rtol=1e-9, atol=1e-12)
    assert not np.allclose(a, b)
    assert gaps.mean() == pytest.approx(1e-3, rel=1e-3)
    assert np.std(gaps) == pytest.approx(1e-3, rel=0.05)  # exponential: sd = mean


def test_zipfian_is_skewed_toward_low_ranks():
    z = ZipfianGenerator(1 << 16, 0.99, np.random.default_rng(3))
    r = z.sample(200_000)
    assert r.min() >= 0 and r.max() < 1 << 16
    counts = np.bincount(r, minlength=1 << 16)
    assert counts[0] > counts[1] > counts[10] > counts[1000]
    share0 = 1.0 / np.sum(1.0 / np.arange(1, (1 << 16) + 1) ** 0.99)
    assert counts[0] / r.size == pytest.approx(share0, rel=0.05)
