"""The harness: refusal without a TPU, and cells, traffic mixes and metrics
found by name, so that a later change adds them as files and entries."""
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import jax

from bench.harness import ROOT, Benchmark, run_cell


def _run_py(cwd: Path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "join-probe-tpch", "--seed", "3",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def _no_result_line(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "metrics" in json.loads(line):
                return False
        except ValueError:
            pass
    return True


def test_refuses_without_a_tpu():
    p = _run_py(ROOT)
    assert p.returncode != 0
    assert _no_result_line(p.stdout)
    assert "no TPU" in p.stderr


def _copy_benchmark(dst: Path) -> None:
    spec = Benchmark(ROOT).spec
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    for path in spec["paths"]:
        shutil.copytree(ROOT / path, dst / path,
                        ignore=shutil.ignore_patterns("__pycache__"))


def test_refuses_from_the_benchmark_files_alone(tmp_path):
    _copy_benchmark(tmp_path)
    p = _run_py(tmp_path)
    assert p.returncode != 0 and _no_result_line(p.stdout)


def test_new_config_traffic_and_metric_are_found_by_name(tmp_path):
    _copy_benchmark(tmp_path)
    b = tmp_path / "bench"
    config = json.loads((b / "configs" / "tpch_sf100_lineitem_u32x4.json").read_text())
    config.update(name="tpch_tiny_u32x4", orders_total=16 * 3000)
    (b / "configs" / "tpch_tiny_u32x4.json").write_text(json.dumps(config))
    traffic = json.loads((b / "traffic" / "tpch_join_probe.json").read_text())
    traffic.update(morsel_keys=512, out_capacity=7 * 512, seg_capacity=7 * 512)
    (b / "traffic" / "tpch_join_tiny.json").write_text(json.dumps(traffic))
    (b / "metrics" / "least_bytes_per_call.py").write_text(
        "def read(record):\n"
        "    work = record.work.get('exec_join')\n"
        "    return work['min_bytes_per_call'] if work else None\n"
    )
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "tpch_tiny_u32x4", "source": "a test", "file": "bench/configs/tpch_tiny_u32x4.json",
        "reduced": ["orders_total"], "why": "a test",
    })
    spec["workloads"].append({
        "name": "join-probe-tiny", "config": "tpch_tiny_u32x4", "traffic": "tpch_join_tiny",
        "chips": 1, "why": "a test",
    })
    for m in spec["end_to_end"]:
        if m["name"] == "probe_keys_per_s":
            m["workloads"].append("join-probe-tiny")
    spec["per_layer"].append({
        "name": "least_bytes_per_call", "unit": "B", "better": "lower", "source": "program_counter",
        "layer": "executors", "moves": "probe_keys_per_s", "workloads": ["join-probe-tiny"],
    })
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    def run(trace):
        return run_cell("join-probe-tiny", seed=11, seconds=1.0, trace=trace,
                        devices=jax.devices()[:1], t_start=time.perf_counter(),
                        root=tmp_path)[0]

    plain = run(False)
    assert plain["correct"] and set(plain["metrics"]) == {"probe_keys_per_s", "setup_s"}
    traced = run(True)
    assert traced["correct"] and traced["metrics"]["least_bytes_per_call"]["value"] > 512 * 12
