"""Both cells, driven end to end on the CPU at tiny sizes: sound runs come
out correct; a timed path broken underneath, and each cell's control, come
out not correct.  The chip check is skipped: ``run_cell`` is called with
the CPU device directly."""
import dataclasses
import time

import jax
import pytest

from bench.control import control_readings
from bench.harness import ROOT, run_cell

TINY = {
    "kv-get-zipf": {
        "config": {"records_total": 1 << 12, "partitions": 1},
        "traffic": {"rate_per_s": 200, "warmup_requests": 8},
    },
    "join-probe-tpch": {
        "config": {"orders_total": 16 * 5000},
        "traffic": {"morsel_keys": 1024, "out_capacity": 7 * 1024, "seg_capacity": 7 * 1024},
    },
}


SEED = 2**31 + 5


@pytest.fixture
def roots(kv_root):
    return {"kv-get-zipf": kv_root, "join-probe-tpch": ROOT}


def run(roots, workload, seed=SEED, trace=False):
    result, info = run_cell(
        workload, seed=seed, seconds=1.0, trace=trace, devices=jax.devices()[:1],
        t_start=time.perf_counter(), root=roots[workload], overrides=TINY[workload],
    )
    return result


@pytest.mark.parametrize("workload", sorted(TINY))
def test_sound_run_is_correct(roots, workload):
    r = run(roots, workload)
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in r["checks"].values())
    assert set(r["metrics"]) >= {"setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert r["device"]["count"] == 1


def test_traced_run_reports_per_layer_metrics(roots):
    r = run(roots, "kv-get-zipf", trace=True)
    assert r["correct"] is True
    # the CPU has no device plane: only the host-side readers find anything
    assert {"frontend_wait_ms", "dispatch_ms", "batch_fill_pct", "build_s", "warm_s"} <= set(
        r["metrics"]
    )
    assert "get_p99_ms" not in r["metrics"]


def _flip_first_answer(monkeypatch):
    from repro.serve_table import batcher

    real = batcher.PendingBatch.scatter

    def scatter(self):
        out = real(self)
        out[0] = 1 - out[0]
        return out

    monkeypatch.setattr(batcher.PendingBatch, "scatter", scatter)


def _drop_half_of_each_batch(monkeypatch):
    from repro.core.hashgraph import EMPTY_KEY
    from repro.serve_table import batcher

    real = batcher.MicroBatcher._coalesce

    def coalesce(self, requests):
        flat, bounds = real(self, requests)
        used = bounds[-1][1]
        return flat.at[used // 2 : used].set(EMPTY_KEY), bounds

    monkeypatch.setattr(batcher.MicroBatcher, "_coalesce", coalesce)


@pytest.mark.parametrize("fault", [_flip_first_answer, _drop_half_of_each_batch])
def test_kv_faults_are_not_correct(roots, monkeypatch, fault):
    fault(monkeypatch)
    r = run(roots, "kv-get-zipf")
    assert r["correct"] is False
    assert r["checks"]["wrong_answers"]["value"] > 0


def _alter_one_value(monkeypatch):
    from repro.core import plans

    real = plans.exec_join

    def exec_join(*args, **kwargs):
        res = real(*args, **kwargs)
        return dataclasses.replace(res, values=res.values.at[0, 1].add(1))

    monkeypatch.setattr(plans, "exec_join", exec_join)


def _probe_half_of_each_morsel(monkeypatch):
    from repro.core import plans
    from repro.core.hashgraph import EMPTY_KEY

    real = plans.exec_join

    def exec_join(table, state, q, **kwargs):
        return real(table, state, q.at[q.shape[0] // 2 :].set(EMPTY_KEY), **kwargs)

    monkeypatch.setattr(plans, "exec_join", exec_join)


def _alter_rows_of_one_late_key(monkeypatch):
    # Rows of one key of the fifth morsel only: a check that compared a
    # few calls' rows, and the rest by count, could miss it.
    import jax.numpy as jnp

    from bench.harness import Benchmark
    from repro.core import plans

    cfg = {**Benchmark().config("tpch_sf100_lineitem_u32x4"), **TINY["join-probe-tpch"]["config"]}
    data = Benchmark().dataset("tpch_lineitem").LineitemShare(
        cfg["orders_total"], cfg["partitions"], SEED
    )
    key = int(data.morsel(4, TINY["join-probe-tpch"]["traffic"]["morsel_keys"])[0])
    real = plans.exec_join

    def exec_join(table, state, q, **kwargs):
        res = real(table, state, q, **kwargs)
        row_key = q[jnp.clip(res.query_idx, 0, q.shape[0] - 1)]
        bad = (res.query_idx >= 0) & (row_key == key)
        return dataclasses.replace(res, values=res.values + bad[:, None].astype(res.values.dtype))

    monkeypatch.setattr(plans, "exec_join", exec_join)


@pytest.mark.parametrize(
    "fault", [_alter_one_value, _probe_half_of_each_morsel, _alter_rows_of_one_late_key]
)
def test_join_faults_are_not_correct(roots, monkeypatch, fault):
    fault(monkeypatch)
    r = run(roots, "join-probe-tpch")
    assert r["correct"] is False
    assert r["checks"]["wrong_keys"]["value"] > 0


def test_controls_fail_the_comparison(kv_root):
    kv = [
        control_readings(
            "kv-get-zipf", seed, 10.0, root=kv_root,
            overrides={"config": {"records_total": 1 << 20, "partitions": 1},
                       "traffic": {"rate_per_s": 20_000}},
        )["wrong_answers"]
        for seed in (1, 2, 3)
    ]
    assert min(kv) > 0
    join = control_readings("join-probe-tpch", 4, 10.0, overrides=TINY["join-probe-tpch"])
    assert join["wrong_keys"] > 0


def test_knee_sweep_reports_each_rate(kv_root):
    from bench.harness import Benchmark
    from bench.sweep import knee, sweep

    bench = Benchmark(kv_root)
    cell = bench.cell("kv-get-zipf", seed=3, seconds=0.5, devices=jax.devices(),
                      overrides=TINY["kv-get-zipf"])
    driver = bench.driver("frontend_open_loop").Driver(cell)
    driver.setup()
    try:
        rows = sweep(driver, cell, [100.0, 200.0])
    finally:
        driver.release()
    assert [r["offered_per_s"] for r in rows] == [100.0, 200.0]
    assert all(r["missing"] == 0 and r["wrong"] == 0 for r in rows)
    assert all(r["compiles_in_window"] == 0 and r["get_p99_ms"] >= r["get_p50_ms"] > 0 for r in rows)
    assert knee(rows) in (0.0, 100.0, 200.0)
    slow = dict(rows[1], completed_per_s=150.0)
    assert knee([rows[0], slow]) == knee([rows[0]])
