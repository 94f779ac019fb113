#!/usr/bin/env python3
"""The benchmark: one run of one cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Cells, metrics and bounds are in BENCHMARK.json at the root of the checkout;
each cell's configuration, traffic mix and correctness limits are files
under bench/, found by name.  The last line of standard output is the
result as one JSON object.  Without a TPU, or with fewer chips than the cell
asks for, the run exits non-zero and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    from bench.lib.harness import run

    run(args.workload, args.seed, args.seconds, bool(args.trace), T_START)


if __name__ == "__main__":
    main()
