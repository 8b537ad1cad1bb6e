"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload kv-get-zipf --seed 7 --seconds 10 --trace 0

Run from the root of a checkout.  The cell (a configuration under a traffic
mix) is looked up in ``BENCHMARK.json``.  The run sets up (data from the
seed, build, warm-up of the cell's own shapes), measures for ``--seconds``,
checks every answer against the plain reference, and prints one JSON object
as the last line of standard output: the end-to-end metrics with
``--trace 0``, the per-layer metrics from a profiler trace with
``--trace 1``.  It exits non-zero and prints no result when JAX finds no
TPU, or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.harness import Benchmark, enable_compile_cache, report, run_cell
    from bench.roofline import peaks_for

    chips = Benchmark(ROOT).workload(args.workload)["chips"]
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX reports {devices[0].platform}", file=sys.stderr)
        return 2
    if len(devices) < chips:
        print(f"{args.workload} needs {chips} TPUs, found {len(devices)}", file=sys.stderr)
        return 2
    try:
        peaks_for(devices[0].device_kind)
    except KeyError as e:
        print(e, file=sys.stderr)
        return 2
    enable_compile_cache(ROOT)
    result, info = run_cell(
        args.workload,
        seed=args.seed,
        seconds=args.seconds,
        trace=bool(args.trace),
        devices=devices,
        t_start=T_START,
        root=ROOT,
    )
    report(result, info)
    return 0


if __name__ == "__main__":
    sys.exit(main())
