#!/usr/bin/env python3
"""Control readings: the plain reference put in the program's place and
computed at the precision below the configuration's (three-pass bf16
matmuls for f32 at ``highest``), held to the cell's own numbers.  Each
limit in ``bench/limits/`` lies between the program's readings and
these.  The benchmark's runs never run this.

    python bench/control.py --workload <cell> --seeds 1,2,3 [--seconds 40]

Prints one JSON line per seed with each number beside its limit and
``"correct"``, which has to come out false.
"""

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import harness  # noqa: E402


def control_line(root: str, workload: str, seed: int, seconds: float,
                 bench_dir: str = BENCH) -> dict:
    cell = harness.load_cell(root, workload, bench_dir)
    harness.import_program(root)
    run = harness.Run(root=root, cell=cell, seed=seed, seconds=seconds, trace=False,
                      out_dir=os.path.join(root, ".bench_out", workload + ".control"))
    driver = harness.load_module(os.path.join(bench_dir, "drivers",
                                              cell.traffic["driver"] + ".py"))
    t0 = time.perf_counter()
    numbers = driver.control(run)
    missing = numbers.pop("missing", 0)
    ok, checks = harness.checks_line(cell.limits, numbers)
    return {"workload": workload, "seed": seed, "correct": bool(ok and not missing),
            "seconds": time.perf_counter() - t0, "checks": checks}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    args = ap.parse_args()
    harness.use_compile_cache(ROOT)
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(control_line(ROOT, args.workload, seed, args.seconds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
