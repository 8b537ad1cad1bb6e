"""Profile one benchmark cell by the program's stages and spans.

    python bench/tools/profile_stages.py --workload join-probe-tpch --seed 7 \
        --seconds 20 [--build] [--overhead SECONDS] [--out FILE]

Runs the cell's driver as ``bench/run.py --trace 1`` does, with the
program's process tracer (``repro.obs.tracing.process_tracer``) enabled
from the start, and prints one JSON object as the last line of standard
output (also written to ``--out``):

* ``setup``: the set-up's host seconds split into what came before the
  driver (imports, backend), the data, the build's packing, compiles and
  device run (spans ``table.build.pack``, ``jax.*`` under ``table.build``,
  ``table.build.run``), and the warm-up;
* ``stages``: each executable's device milliseconds per call by stage, the
  union of the stage's ops (``bench.trace_stages``), in a profiled window;
  the "other" share; the idle gaps by program and JAX span; the device's
  clock offset;
* ``trace``: the numbers ``bench.trace_reduce`` reads from the same trace;
* ``build_stages`` (``--build``): a second build, profiled, by ``build.*``
  stage;
* ``overhead`` (``--overhead``): the probe rate in unprofiled windows of
  that length with the tracer off and on (off, on, off, on), and the host
  cost of one span.

It refuses without a TPU, as ``bench/run.py`` does.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def _profiled(fn):
    """Run ``fn()`` under the JAX profiler; return its ``.xplane.pb`` bytes."""
    import jax

    with tempfile.TemporaryDirectory() as tmp:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=options)
        try:
            fn()
        finally:
            jax.profiler.stop_trace()
        planes = sorted(Path(tmp).rglob("*.xplane.pb"))
        return planes[-1].read_bytes() if planes else None


def _span_seconds(records, name):
    return sum(r.seconds for r in records if r.name == name)


def _under(records, root):
    """Records whose chain of parents reaches a span named ``root``."""
    names = {root}
    changed = True
    while changed:
        changed = False
        for r in records:
            if r.parent in names and r.name not in names and not r.name.startswith("jax."):
                names.add(r.name)
                changed = True
    return [r for r in records if r.parent in names]


def _setup_breakdown(records, spans, t_driver, setup_s):
    build = [r for r in records if r.name == "table.build"]
    pack = _span_seconds(records, "table.build.pack")
    run = _span_seconds(records, "table.build.run")
    under = _under(records, "table.build")
    compiles = sum(r.seconds for r in under if r.name.startswith("jax."))
    run_compiles = sum(
        r.seconds for r in under if r.name.startswith("jax.") and r.parent == "table.build.run"
    )
    pack_compiles = compiles - run_compiles
    out = {
        "setup_s": setup_s,
        "before_driver_s": t_driver,
        "data_s": spans.get("data_s"),
        "build_s": spans.get("build_s"),
        "warm_s": spans.get("warm_s"),
        "table.build_s": sum(r.seconds for r in build),
        "build_pack_s": pack - pack_compiles,
        "build_compile_s": compiles,
        "build_device_s": run - run_compiles,
        "compiles_by_kind_s": {
            k: sum(r.seconds for r in under if r.name == k)
            for k in ("jax.trace", "jax.lower", "jax.compile")
        },
    }
    parts = [out[k] for k in ("before_driver_s", "data_s", "build_s", "warm_s")]
    out["unattributed_s"] = setup_s - sum(p or 0.0 for p in parts)
    out["build_parts_over_build_s"] = (
        (out["build_pack_s"] + out["build_compile_s"] + out["build_device_s"]) / out["build_s"]
        if out["build_s"]
        else None
    )
    return out


def _stage_report(summary, jit):
    from repro.obs.tracing import STAGES

    if summary is None or not summary.calls.get(jit):
        return None
    per_call = {s: summary.per_call_ms(jit, s) for s in (*STAGES, "other")}
    per_call = {s: v for s, v in per_call.items() if v}
    module_ms = 1e3 * summary.module_s[jit] / summary.calls[jit]
    return {
        "calls": summary.calls[jit],
        "module_ms_per_call": module_ms,
        "stage_ms_per_call": per_call,
        "stages_over_module": sum(per_call.values()) / module_ms,
        "other_share": summary.other_share(jit),
        "idle_gaps": summary.idle_gaps,
        "clock_offset_ms": summary.clock_offset_ms,
    }


def _span_cost_us(n=20000):
    from repro.obs.tracing import Tracer

    out = {}
    for enabled in (False, True):
        tracer = Tracer(enabled=enabled, ring=256)
        t0 = time.perf_counter()
        for _ in range(n):
            with tracer.span("plan.join"):
                pass
        out["on" if enabled else "off"] = 1e6 * (time.perf_counter() - t0) / n
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--build", action="store_true", help="profile a second build")
    ap.add_argument("--overhead", type=float, default=0.0,
                    help="seconds of each unprofiled window with the tracer off and on")
    ap.add_argument("--out", type=Path)
    args = ap.parse_args(argv)

    import jax

    from bench.harness import Benchmark, enable_compile_cache
    from bench.trace_reduce import reduce_file
    from bench.trace_stages import reduce_stages_file
    from repro.obs.tracing import hlo_stages, process_tracer

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX reports {devices[0].platform}", file=sys.stderr)
        return 2
    enable_compile_cache(ROOT)
    tracer = process_tracer()
    tracer.enabled = True
    bench = Benchmark(ROOT)
    cell = bench.cell(args.workload, seed=args.seed, seconds=args.seconds, trace=True,
                      devices=devices)
    driver = bench.driver(cell.traffic["driver"]).Driver(cell)
    t_driver = time.perf_counter() - T_START
    driver.setup()
    setup_s = time.perf_counter() - T_START
    records = tracer.recent()
    result = {
        "workload": args.workload,
        "seed": args.seed,
        "device": devices[0].device_kind,
        "setup": _setup_breakdown(records, driver.spans, t_driver, setup_s),
    }

    plan, table = driver.plan, driver.plan.table
    stage_of = {"exec_join": hlo_stages(plan.lower(driver.state, driver.morsels[0])
                                         .compile().as_text())}
    with tempfile.TemporaryDirectory() as tmp:
        trace = Path(tmp) / "window.xplane.pb"
        trace.write_bytes(_profiled(driver.window))
        old = reduce_file(trace)
        new = reduce_stages_file(trace, stage_of)
    ex = old.executable("exec_join") if old else None
    result["trace"] = {
        "busy_s": old.busy_s,
        "window_s": old.window_s,
        "idle_pct": 100.0 * old.idle_share,
        "exec_join": ex,
        "exec_join_ms_per_call": 1e3 * ex["seconds"] / ex["count"] if ex else None,
        "device_ops": old.device_ops,
    } if old else None
    result["stages"] = _stage_report(new, "exec_join")

    if args.overhead:
        cell.seconds = args.overhead
        rates = []
        for enabled in (False, True, False, True):
            tracer.enabled = enabled
            driver.window()
            rates.append({"tracer": enabled, "calls": len(driver.totals),
                          "probe_keys_per_s": len(driver.totals) * driver.morsel_keys
                          / driver.window_s})
        tracer.enabled = True
        result["overhead"] = {"windows": rates, "span_cost_us": _span_cost_us()}

    driver.release()
    checks = driver.check()
    result["checks"] = {c.name: c.value for c in checks}
    result["correct"] = all(c.ok for c in checks)

    if args.build:
        keys, values = driver.data.table()
        k = table.schema.pack_keys(keys, table.key_sharding())
        v = table.schema.pack_values(values, table.key_sharding())
        build_jit = type(table)._build_values_jit
        stage_of = {"_build_values_jit": hlo_stages(
            build_jit.lower(table, k, v, hash_range=table.hash_range).compile().as_text())}

        def build():
            with jax.profiler.TraceAnnotation("bench.window"):
                jax.block_until_ready(table.build(k, v))

        with tempfile.TemporaryDirectory() as tmp:
            trace = Path(tmp) / "build.xplane.pb"
            trace.write_bytes(_profiled(build))
            s = reduce_stages_file(trace, stage_of)
        result["build_stages"] = None if s is None else {
            "device_s": s.module_s.get("_build_values_jit"),
            "stage_s": s.stage_s.get("_build_values_jit"),
            "idle_gaps": s.idle_gaps,
        }

    line = json.dumps(result)
    if args.out:
        args.out.parent.mkdir(parents=True, exist_ok=True)
        args.out.write_text(line + "\n")
    print(line, flush=True)
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
