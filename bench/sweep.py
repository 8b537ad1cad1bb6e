"""Find the knee of an open-loop cell: the highest offered rate it sustains.

    python bench/sweep.py --workload kv-get-zipf --seed 3 --seconds 5 \\
        --rates 1000,2000,4000,8000

Sets the cell up once, then runs one window per rate through the same
server and front end, and prints a line per rate: offered and completed
requests per second, p50 and p99 latency from intended arrival, how late
the generator ran, and answers that differ from the reference; then the
knee: the highest offered rate that was sustained, meaning at least 97% of
it completed, the generator ran less than 50 ms late at p99, and p99
latency stayed under 100 ms.  A cell's traffic file records the knee found
and offers 4/5 of it.  Needs a TPU.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def sweep(driver, cell, rates) -> list:
    rows = []
    for rate in rates:
        cell.traffic["rate_per_s"] = rate
        driver._make_requests()
        driver.window()
        driver.collect()
        rows.append({
            "offered_per_s": rate,
            **driver.latency(),
            "missing": driver.missing,
            "wrong": driver.wrong_answers(driver.answers),
            "compiles_in_window": driver.compiles_in_window,
        })
        print(json.dumps(rows[-1]), flush=True)
    return rows


def knee(rows) -> float:
    """The highest offered rate the sweep sustained (0 when none was)."""
    ok = [
        r["offered_per_s"] for r in rows
        if r["completed_per_s"] >= 0.97 * r["offered_per_s"]
        and r["generator_late_p99_ms"] < 50 and r["get_p99_ms"] < 100 and not r["missing"]
    ]
    return max(ok, default=0.0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True, help="comma-separated requests/s")
    args = ap.parse_args(argv)

    import jax

    from bench.harness import Benchmark, enable_compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX reports {devices[0].platform}", file=sys.stderr)
        return 2
    enable_compile_cache(ROOT)
    bench = Benchmark(ROOT)
    cell = bench.cell(args.workload, seed=args.seed, seconds=args.seconds, devices=devices)
    driver = bench.driver(cell.traffic["driver"]).Driver(cell)
    driver.setup()
    print(f"setup_s {time.perf_counter() - T_START:.3f} {driver.spans}", flush=True)
    rows = sweep(driver, cell, [float(r) for r in args.rates.split(",")])
    driver.release()
    print(json.dumps({"knee_per_s": knee(rows)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
