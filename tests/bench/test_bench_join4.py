"""The four-chip join cell, driven end to end on four CPU devices at a tiny
size whose lineitem count (79,999) is not a multiple of the devices, so
the build pads: a sound run comes out correct, a timed path broken
underneath and the cell's control do not, and a traced run reports the
exchange's fill.  The chip check is skipped: ``run_cell`` is given the
CPU devices directly."""
import dataclasses
import time
from types import SimpleNamespace

import jax
import pytest

from bench.control import control_readings
from bench.harness import ROOT, Benchmark, run_cell

CELL = "join-probe-tpch-4chip"
TINY = {
    "config": {"orders_total": 4 * 20001},
    "traffic": {"morsel_keys": 4 * 1024, "out_capacity": 7 * 1024, "seg_capacity": 7 * 1024},
}
SEED = 2**33 + 17


def run(trace=False):
    result, info = run_cell(
        CELL, seed=SEED, seconds=1.0, trace=trace, devices=jax.devices()[:4],
        t_start=time.perf_counter(), root=ROOT, overrides=TINY,
    )
    return result, info


def test_rows_do_not_divide_over_the_chips():
    cfg = {**Benchmark().config("tpch_sf100_lineitem_u32x4_4chip"), **TINY["config"]}
    data = Benchmark().dataset(cfg["dataset"]).LineitemShare(
        cfg["orders_total"], cfg["partitions"], SEED
    )
    assert data.rows % 4 == 3
    full = Benchmark().config("tpch_sf100_lineitem_u32x4_4chip")
    assert Benchmark().dataset("tpch_lineitem").LineitemShare(
        full["orders_total"], full["partitions"], SEED
    ).rows == 149_999_997


def test_sound_run_is_correct_and_counts_the_exchange():
    r, info = run()
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["device"]["count"] == 4
    assert set(r["metrics"]) == {"probe_keys_per_s", "setup_s"}
    # 1024 probes a chip in slots of default_capacity(1024, 4, 1.25) = 328 per peer
    assert info["exchange_fill_pct.route"] == pytest.approx(100 * 1024 / (4 * 328))
    assert 0 < info["exchange_fill_pct.return"] < 100


def test_traced_run_reports_the_exchange_fill():
    r, info = run(trace=True)
    assert r["correct"] is True
    # the CPU has no device plane: the roofline reader finds nothing
    assert set(r["metrics"]) == {"exchange_fill_pct"}
    route, back = info["exchange_fill_pct.route"], info["exchange_fill_pct.return"]
    assert min(route, back) < r["metrics"]["exchange_fill_pct"]["value"] < max(route, back)


def _alter_one_value(monkeypatch):
    from repro.core import plans

    real = plans.exec_join

    def exec_join(*args, **kwargs):
        res = real(*args, **kwargs)
        return dataclasses.replace(res, values=res.values.at[0, 1].add(1))

    monkeypatch.setattr(plans, "exec_join", exec_join)


def _lose_one_chips_rows(monkeypatch):
    from repro.core import plans

    real = plans.exec_join

    def exec_join(*args, **kwargs):
        res = real(*args, **kwargs)
        return dataclasses.replace(res, num_results=res.num_results.at[3].set(0))

    monkeypatch.setattr(plans, "exec_join", exec_join)


@pytest.mark.parametrize("fault", [_alter_one_value, _lose_one_chips_rows])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    r, _ = run()
    assert r["correct"] is False
    assert r["checks"]["wrong_keys"]["value"] > 0


def test_control_fails_the_comparison():
    readings = control_readings(CELL, 4, 10.0, overrides=TINY)
    assert readings["wrong_keys"] > 0


def test_roofline_share_counts_each_call_once():
    # 8 module events over 4 devices are 2 calls; 4 device-seconds at
    # 1 B/s against 2 calls of 1 B each read 50%
    reader = Benchmark().reader("exec_join_roofline.4chip")
    trace = SimpleNamespace(
        devices=4, executable=lambda name: {"count": 8, "seconds": 4.0}
    )
    record = SimpleNamespace(
        trace=trace, peaks={"hbm_bytes_per_s": 1.0},
        work={"exec_join": {"min_bytes_per_call": 1}},
    )
    assert reader.read(record) == pytest.approx(50.0)
    assert reader.read(SimpleNamespace(trace=None, peaks={}, work={})) is None


def test_fill_reader_finds_nothing_without_the_counters():
    reader = Benchmark().reader("exchange_fill_pct")
    assert reader.read(SimpleNamespace(counters={}, work={})) is None
