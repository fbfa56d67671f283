#!/usr/bin/env python3
"""The benchmark: one run of one cell.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  It loads the cell named in
``BENCHMARK.json``, makes its inputs from the seed, warms up, measures
for ``--seconds``, checks what the timed path produced against the plain
reference (``bench/reference.py``), and prints one JSON line last on
standard output:

    {"correct": ..., "attempted": ..., "failed": ..., "metrics": {...},
     "device": {...}, ["breakdown": {...},] "checks": {...}}

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, read from a ``jax.profiler`` trace of the window and
the program's ``RCCA_TRACE`` spans.  The last lines on standard error
are the compared numbers beside their limits.

It measures only on a TPU: with no TPU, too few chips, or a device kind
missing from ``bench/peaks.json`` it exits non-zero and prints no
result.  Compiled programs are cached in ``.bench_cache/`` of the
checkout.  How to add a cell, configuration, traffic mix or metric:
``bench/README.md``.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def process_start() -> float:
    """The process's start on the ``time.perf_counter`` clock, from
    ``/proc`` where it exists, else this module's first line."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        since_start = time.clock_gettime(time.CLOCK_BOOTTIME) - ticks / os.sysconf("SC_CLK_TCK")
        return min(T_START, time.perf_counter() - since_start)
    except (OSError, ValueError, IndexError, AttributeError):
        return T_START


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # the TPU runtime's logs stay inside the checkout, not in a fixed /tmp path
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(ROOT, ".bench_out", "tpu_logs"))
    os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)
    sys.path.insert(0, BENCH)
    import harness

    try:
        line = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                                bool(args.trace), t_start=process_start())
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
