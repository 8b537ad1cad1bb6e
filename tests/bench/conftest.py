"""The benchmark's CPU tests import ``bench`` from the checkout's root.

The ``kv-get-zipf`` cell is not listed in ``BENCHMARK.json`` until its
knee is measured on the chip; ``kv_root`` is a copy of the benchmark with
the cell's entries (``data/kv_cell.json``) added, so that its tests drive
the same files by name.
"""
import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def copy_benchmark(dst: Path) -> None:
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    for path in spec["paths"]:
        shutil.copytree(ROOT / path, dst / path, ignore=shutil.ignore_patterns("__pycache__"))


def add_kv_cell(root: Path) -> None:
    entries = json.loads((Path(__file__).parent / "data" / "kv_cell.json").read_text())
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append(entries["config"])
    spec["workloads"].append(entries["workload"])
    spec["end_to_end"][:0] = entries["end_to_end"]
    for m in spec["per_layer"]:
        if m["name"] in ("build_s", "warm_s"):
            m["workloads"].append(entries["workload"]["name"])
    spec["per_layer"] += entries["per_layer"]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


@pytest.fixture(scope="session")
def kv_root(tmp_path_factory) -> Path:
    root = tmp_path_factory.mktemp("kv_root")
    copy_benchmark(root)
    add_kv_cell(root)
    return root
