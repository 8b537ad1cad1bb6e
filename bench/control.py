"""Run a cell's control at the cell's size: the comparison must fail it.

    python bench/control.py --workload kv-get-zipf --seeds 11,12,13

The control is the cell's plain reference put in the program's place with
one guarantee of the configuration broken (see ``control`` in the cell's
driver).  It prints, per seed, the numbers the comparison computes for it;
each has to exceed its limit, which is how the limits were bounded from
above.  The benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def control_readings(workload: str, seed: int, seconds: float, root: Path = ROOT,
                     overrides=None) -> dict:
    from bench.harness import Benchmark

    bench = Benchmark(root)
    cell = bench.cell(workload, seed=seed, seconds=seconds, overrides=overrides)
    module = bench.driver(cell.traffic["driver"])
    driver = module.Driver(cell)
    driver.prepare()
    return module.control(driver)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=None,
                    help="window length the requests are drawn for (default: run_seconds)")
    args = ap.parse_args(argv)
    from bench.harness import Benchmark

    seconds = args.seconds or Benchmark(ROOT).spec["run_seconds"]
    for seed in (int(s) for s in args.seeds.split(",")):
        readings = control_readings(args.workload, seed, seconds)
        print(json.dumps({"workload": args.workload, "seed": seed, **readings}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
