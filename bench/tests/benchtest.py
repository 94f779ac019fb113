"""Shared helpers of the benchmark's tests: run a tiny cell on the CPU."""

import io
import os
import time

DATA = os.path.join(os.path.dirname(__file__), "data")
BENCHMARK = os.path.join(DATA, "BENCHMARK.json")
SEED = 2**40 + 11


def run_tiny(cell: str, fault=None, trace=False, seconds=0.2):
    """One run of a tiny cell with the look for a chip skipped; returns the
    result and what the run wrote to standard error."""
    from bench.lib.harness import run

    out, err = io.StringIO(), io.StringIO()
    result = run(cell, SEED, seconds, trace, time.perf_counter(), allow_cpu=True,
                 fault=fault, benchmark=BENCHMARK, out=out, err=err)
    return result, err.getvalue()
