"""``chip_smoke.py`` off the chip: it refuses the CPU, and its serving path
and reference checks pass on small tables over CPU devices."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

import chip_smoke

ROOT = Path(__file__).resolve().parents[1]


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")],
        env=env,
        cwd=ROOT,
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr


@pytest.mark.parametrize("chips", [1, 4])
def test_serving_path_matches_reference(chips, capsys):
    if len(jax.devices()) < chips:
        pytest.skip(f"needs {chips} devices")
    chip_smoke.run(jax.devices()[:chips], rows_log2=11, seed=1, requests=120)
    out = capsys.readouterr().out
    assert "reference mismatches, queries_after_writes: 0" in out
    assert "1 folds" in out
    assert "aot_misses=0 dropped_rows=0 failed_requests=0 open_traces=0" in out


def test_reference_model_tracks_writes():
    keys = np.array([5, 7, 5, 9], np.uint64)
    values = np.arange(24, dtype=np.int32).reshape(4, 6)
    ref = chip_smoke.Reference(keys, values)
    q = np.array([5, 7, 9, 11], np.uint64)
    np.testing.assert_array_equal(ref.counts(q), [2, 1, 1, 0])
    np.testing.assert_array_equal(ref.rows(5), values[[0, 2]])
    ref.upsert(np.array([5, 11, 5], np.uint64), values[[1, 2, 3]])
    ref.delete(np.array([7], np.uint64))
    np.testing.assert_array_equal(ref.counts(q), [1, 0, 1, 1])
    np.testing.assert_array_equal(ref.rows(5), values[[3]])  # last writer wins
    assert ref.rows(7).shape == (0, 6)


def test_verdict_line_names_the_device(monkeypatch, capsys):
    """With a TPU reported, the last stdout line is exactly the verdict."""

    class FakeTpu:
        platform = "tpu"
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda: [FakeTpu()])
    monkeypatch.setattr(chip_smoke, "run", lambda *a, **k: None)
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"])
    monkeypatch.setattr(
        "repro.utils.compile_cache.enable_compile_cache", lambda: "(cache)"
    )
    assert chip_smoke.main() == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }
