#!/usr/bin/env python3
"""Offered-rate sweep of a serving cell, to find its knee (run on a TPU).

    python bench/sweep_serve.py --workload <serving cell> --rates 100,200,400 --seconds 8

One process, one set-up; for each rate an open-loop window of the
cell's traffic at that rate.  Prints per rate the latency quartiles,
p95, and whether the backlog grew: the median latency of the last
quarter of requests (by due time) against the first quarter's.  The
knee is the highest rate whose backlog does not grow; the cell's
traffic file fixes its rate at 0.8 of it.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import corpora  # noqa: E402
import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    cell = harness.load_cell(ROOT, args.workload)
    harness.use_compile_cache(ROOT)
    harness.find_chip(cell.chips)
    harness.import_program(ROOT)
    out = os.path.join(ROOT, ".bench_out", args.workload + ".sweep")
    run = harness.Run(root=ROOT, cell=cell, seed=args.seed, seconds=args.seconds,
                      trace=False, out_dir=out)
    driver = harness.load_module(os.path.join(BENCH, "drivers", "serve.py"))
    st = driver.setup(run)
    tr = cell.traffic
    for rate in (float(r) for r in args.rates.split(",")):
        st["sched"] = corpora.request_schedule(
            args.seed, rate=rate, seconds=args.seconds, pool=tr["pool"],
            popularity_zipf=tr["popularity_zipf"], share_a=tr["share_a"])
        t0 = time.perf_counter()
        m = driver.window(run, st)
        lat = run.records["latency_s"] * 1e3
        q = len(lat) // 4
        first, last = np.median(lat[:q]), np.median(lat[-q:])
        print(json.dumps({
            "rate": rate, "requests": int(len(lat)), "failed": m["failed"],
            "p50_ms": float(np.median(lat)), "p95_ms": m["serve_p95_ms"],
            "first_quarter_p50_ms": float(first), "last_quarter_p50_ms": float(last),
            "backlog_grows": bool(last > 2 * first + 5.0),
            "gen_late_p95_ms": float(np.percentile(run.records["gen_late_s"], 95)) * 1e3,
            "batches": run.records["batches"], "wall_s": time.perf_counter() - t0}),
            flush=True)
    st["proj"].close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
