#!/usr/bin/env python3
"""Readings that the correctness limits are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 [--control-seeds 1,2,3]
                              [--fault-seeds 1,2,3] [--out FILE]

For each seed: the numbers compared for ``correct`` from a sound run of the
program (set-up's first steps, or a short window of requests), and, on the
control and fault seeds, the same numbers with the control in the program's
place (the reference in bfloat16 at the default precision) and with each
fault the cell can have planted in the timed path.  One process, so every
program compiles once.  Prints one JSON line per reading and a summary.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]


def _seeds(text):
    return [int(s) for s in text.split(",") if s] if text else []


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--seconds", type=float, default=2.0,
                    help="window of a sampling run (requests to check)")
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    readings = calibrate(args.workload, _seeds(args.seeds), _seeds(args.control_seeds),
                         _seeds(args.fault_seeds), args.seconds)
    summary = summarize(readings)
    print(json.dumps({"summary": summary}), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"readings": readings, "summary": summary}, f, indent=1)


def calibrate(workload, seeds, control_seeds, fault_seeds, seconds=2.0,
              benchmark=None, allow_cpu=False):
    """``benchmark`` and ``allow_cpu`` are for the tests: tiny cells, and no
    look for a chip."""
    import jax

    from bench.lib import harness
    from bench.lib import spec as specs

    cell = specs.load_cell(workload, benchmark)
    harness.require_chips(cell.chips, allow_cpu)
    from repro.utils.cache import enable_compile_cache

    if not allow_cpu:  # the tests on the CPU leave the cache off
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    mod = specs.kind_module(cell)
    out = []

    def emit(kind, seed, numbers, t0):
        row = {"kind": kind, "seed": seed, "numbers": numbers,
               "seconds": time.perf_counter() - t0}
        print(json.dumps(row), flush=True)
        out.append(row)

    for seed in sorted(set(seeds) | set(control_seeds) | set(fault_seeds)):
        t0 = time.perf_counter()
        run = mod.checked_run(cell, seed, seconds)
        ref = run.reference()
        if seed in seeds:
            emit("sound", seed, run.numbers(ref), t0)
        if seed in control_seeds:
            t0 = time.perf_counter()
            emit("control", seed, run.control(ref), t0)
        if seed in fault_seeds:
            for fault in mod.calibration_faults(cell):
                t0 = time.perf_counter()
                bad = mod.checked_run(cell, seed, seconds, fault=fault)
                emit(f"fault:{fault}", seed, bad.numbers(bad.reference()), t0)
    return out


def summarize(readings):
    """Per number: the largest sound reading (the lower reading), and per
    control or fault the smallest (an upper reading)."""
    summary = {}
    for row in readings:
        for k, v in row["numbers"].items():
            entry = summary.setdefault(k, {})
            if row["kind"] == "sound":
                entry["sound_max"] = max(entry.get("sound_max", 0.0), v)
            else:
                key = f"{row['kind']}_min"
                entry[key] = min(entry.get(key, float("inf")), v)
    return summary


if __name__ == "__main__":
    main()
