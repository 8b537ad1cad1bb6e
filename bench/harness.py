"""The benchmark harness: finds a cell's files by name and runs the cell once.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name that
``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json`` — the deployment: sizes, serving
  settings, guarantees, source; it names its data set;
* ``bench/datasets/<dataset>.py`` — the seeded generator of that data and
  its plain reference;
* ``bench/traffic/<traffic>.json`` — the traffic mix's parameters; it names
  the general driver that reads it;
* ``bench/drivers/<driver>.py`` — a general driver: set-up, the measured
  window, the comparison that decides ``correct``;
* ``bench/metrics/<metric>.py`` — the reader of one per-layer metric.

A driver module defines ``Driver(cell)`` with ``prepare()`` (host data
only), ``setup()``, ``window()``, ``release()``, ``check() -> [Check]`` and
``outcome() -> Outcome``, and ``control(driver)`` for ``bench/control.py``.
A reader module defines ``read(record) -> float | None``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import sys
import tempfile
import time
from pathlib import Path
from types import ModuleType, SimpleNamespace
from typing import Optional

ROOT = Path(__file__).resolve().parent.parent


@dataclasses.dataclass(frozen=True)
class Check:
    """One number the comparison computed, and the largest value it may take."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    """What a driver reports after its window and its check."""

    end_to_end: dict  # metric name -> value (host clock), without setup_s
    attempted: int
    failed: int
    spans: dict  # set-up span name -> seconds (host clock)
    counters: dict  # program counters over the window
    work: dict  # quantities computed from shapes, for the readers
    info: dict = dataclasses.field(default_factory=dict)  # printed, not judged


class CompileCounter:
    """Counts JAX traces and backend compiles (cache hits included) from the
    moment :meth:`arm` is called."""

    EVENTS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        import jax

        self.count = 0
        self._armed = False
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    def _listen(self, event, duration, **kwargs) -> None:
        if self._armed and event in self.EVENTS:
            self.count += 1

    def arm(self) -> None:
        self.count = 0
        self._armed = True

    def disarm(self) -> int:
        self._armed = False
        return self.count


@dataclasses.dataclass
class Cell:
    """One cell's run: its files, its arguments and its devices."""

    name: str
    chips: int
    seed: int
    seconds: float
    trace: bool
    devices: list
    config: dict
    traffic: dict
    dataset: ModuleType
    compiles: CompileCounter

    def span(self, name: str):
        """A host span in the profiler's trace (traced runs only)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def window_span(self):
        """Marks the measured window; the trace reduction reads only it."""
        from bench.trace_reduce import WINDOW_SPAN

        return self.span(WINDOW_SPAN)


def load_module(path: Path) -> ModuleType:
    """Import the file at ``path`` (names may hold dots, so not by import path)."""
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    name = "bench_file_" + "".join(c if c.isalnum() else "_" for c in str(path))
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Benchmark:
    """``BENCHMARK.json`` and the files it names, under ``root``."""

    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())

    def workload(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return json.loads((self.root / "bench" / "traffic" / f"{name}.json").read_text())

    def dataset(self, name: str) -> ModuleType:
        return load_module(self.root / "bench" / "datasets" / f"{name}.py")

    def driver(self, name: str) -> ModuleType:
        return load_module(self.root / "bench" / "drivers" / f"{name}.py")

    def reader(self, metric: str) -> ModuleType:
        return load_module(self.root / "bench" / "metrics" / f"{metric}.py")

    def cell(self, workload: str, *, seed: int, seconds: float, trace: bool = False,
             devices=(), overrides: Optional[dict] = None) -> Cell:
        """The cell ``workload`` with its files loaded.  ``overrides``
        (``{"config": {...}, "traffic": {...}}``) replaces entries of its
        files: the CPU tests run the real path at tiny sizes."""
        w = self.workload(workload)
        overrides = overrides or {}
        config = {**self.config(w["config"]), **overrides.get("config", {})}
        traffic = {**self.traffic(w["traffic"]), **overrides.get("traffic", {})}
        return Cell(
            name=workload,
            chips=w["chips"],
            seed=int(seed) % (1 << 64),
            seconds=float(seconds),
            trace=bool(trace),
            devices=list(devices)[: w["chips"]],
            config=config,
            traffic=traffic,
            dataset=self.dataset(config["dataset"]),
            compiles=CompileCounter(),
        )

    @staticmethod
    def applies(metric: dict, cell: str) -> bool:
        return "workloads" not in metric or cell in metric["workloads"]

    def end_to_end(self, cell: str) -> list:
        return [m for m in self.spec["end_to_end"] if self.applies(m, cell)]

    def per_layer(self, cell: str) -> list:
        return [m for m in self.spec["per_layer"] if self.applies(m, cell)]


def enable_compile_cache(root: Path = ROOT) -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR`` when
    set, else the fixed directory ``<checkout>/.jax_cache``."""
    import os

    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def memory_peak_bytes(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def run_cell(
    workload: str,
    *,
    seed: int,
    seconds: float,
    trace: bool,
    devices,
    t_start: float,
    root: Path = ROOT,
    overrides: Optional[dict] = None,
) -> tuple[dict, dict]:
    """Run one cell once; return the result line as a dict, and what the run
    saw besides (set-up spans, generator lateness) for standard error.
    ``overrides``: see :meth:`Benchmark.cell`.
    """
    from bench.roofline import peaks_for

    bench = Benchmark(root)
    cell = bench.cell(workload, seed=seed, seconds=seconds, trace=trace,
                      devices=devices, overrides=overrides)
    devices = cell.devices
    peaks = peaks_for(devices[0].device_kind) if devices[0].platform == "tpu" else {}
    driver = bench.driver(cell.traffic["driver"]).Driver(cell)
    driver.setup()
    setup_s = time.perf_counter() - t_start

    summary = None
    if trace:
        import jax

        from bench.trace_reduce import reduce_file

        with tempfile.TemporaryDirectory() as tmp:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(tmp, profiler_options=options)
            try:
                driver.window()
            finally:
                jax.profiler.stop_trace()
            planes = sorted(Path(tmp).rglob("*.xplane.pb"))
            summary = reduce_file(planes[-1]) if planes else None
    else:
        driver.window()
    peak = memory_peak_bytes(devices)
    driver.release()
    gc.collect()
    checks = driver.check()
    outcome = driver.outcome()

    dev = devices[0]
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak,
    }
    if trace:
        record = SimpleNamespace(
            workload=workload,
            spans=outcome.spans,
            counters=outcome.counters,
            work=outcome.work,
            trace=summary,
            peaks=peaks,
        )
        metrics = {}
        for m in bench.per_layer(workload):
            value = bench.reader(m["name"]).read(record)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        if summary is not None:
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
    else:
        values = {**outcome.end_to_end, "setup_s": setup_s}
        metrics = {
            m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in bench.end_to_end(workload)
        }
    result = {
        "correct": all(c.ok for c in checks),
        "attempted": int(outcome.attempted),
        "failed": int(outcome.failed),
        "metrics": metrics,
        "device": device,
    }
    if summary is not None:
        result["breakdown"] = {
            "device_ops": summary.device_ops,
            "idle_gaps": summary.idle_gaps,
        }
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit} for c in checks}
    info = {**outcome.info, **{f"setup.{k}": v for k, v in outcome.spans.items()}}
    return result, info


def report(result: dict, info: dict, out=sys.stdout, err=sys.stderr) -> None:
    """Print what the run saw (``info``), then the checks as the last lines
    of standard error, and the result as the last line of standard output."""
    out.flush()
    for name, value in info.items():
        print(f"info {name}: {value}", file=err)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=err)
    err.flush()
    print(json.dumps(result), file=out, flush=True)
