"""One run of one cell: set-up, the measured window, the check, the result.

The last line of standard output is the result's JSON object; the numbers
compared for ``correct`` are also the last lines of standard error, each
with its limit.  With ``--trace 1`` the window runs under the profiler and
the result carries the cell's per-layer metrics instead of its end-to-end
ones.
"""

from __future__ import annotations

import importlib.util
import json
import math
import os
import sys
import tempfile
import time

from bench.lib import spec as specs
from bench.lib import trace as tr


class CompileClock:
    """Counts, by phase, the programs compiled (persistent-cache misses),
    those loaded from the cache, and the seconds of both."""

    def __init__(self):
        import jax

        self.phase = "setup"
        self.count: dict = {}
        self.seconds: dict = {}
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _add(self, what, n=1):
        key = f"{self.phase}.{what}"
        self.count[key] = self.count.get(key, 0) + n

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_misses":
            self._add("compiled")
        elif event == "/jax/compilation_cache/cache_hits":
            self._add("cached")

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds[self.phase] = self.seconds.get(self.phase, 0.0) + duration


def require_chips(n: int, allow_cpu: bool = False):
    import jax

    devices = jax.devices()
    if not allow_cpu and devices[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {devices[0].platform} devices; "
                         "the benchmark measures the chip only")
    if len(devices) < n:
        raise SystemExit(f"the cell needs {n} chips, JAX found {len(devices)}")
    return devices[:n]


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks))


def _reader(name: str):
    path = os.path.join(specs.BENCH_DIR, "metrics", f"{name}.py")
    mod_spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def _finite(v: float) -> float:
    """JSON has no NaN or infinity: a number that is not finite reads 1e30."""
    return v if math.isfinite(v) else 1e30


def run(workload: str, seed: int, seconds: float, trace: bool, t_start: float,
        allow_cpu: bool = False, fault=None, benchmark=None,
        out=sys.stdout, err=sys.stderr) -> dict:
    """``allow_cpu``, ``fault`` and ``benchmark`` are for the tests: they
    skip the look for a chip, plant a fault, and name tiny cells."""
    import jax

    cell = specs.load_cell(workload, benchmark)
    devices = require_chips(cell.chips, allow_cpu)
    from repro.utils.cache import enable_compile_cache

    if not allow_cpu:  # the tests on the CPU leave the cache off
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    peaks = specs.peaks(devices[0].device_kind) if not allow_cpu else None
    clock = CompileClock()
    autotune = _autotune_file()
    autotune_before = os.path.exists(autotune)

    mod = specs.kind_module(cell)
    run_ = mod.Run(cell, seed, fault=fault)
    run_.setup()
    stamps = {}
    logdir = tempfile.TemporaryDirectory() if trace else None

    def on_open():  # set-up ends where the window opens
        stamps["setup_s"] = time.perf_counter() - t_start
        if trace:
            jax.profiler.start_trace(logdir.name)
        clock.phase = "window"

    def on_close():
        clock.phase = "after"
        if trace:
            jax.profiler.stop_trace()

    win = run_.window(min(seconds, cell.traffic["trace_seconds"]) if trace else seconds,
                      spans=trace, on_open=on_open, on_close=on_close)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak(devices)}
    metrics, breakdown = {}, None
    if trace:
        reduced = tr.load_dir(logdir.name)
        logdir.cleanup()
        window = tr.window_of(reduced)
        summary = tr.summarize(reduced, window)
        busy = [d["busy_ns"] for d in summary["devices"]]
        device["busy_s"] = sum(busy) / len(busy) / 1e9
        device["window_s"] = (window[1] - window[0]) / 1e9
        ctx = {"summary": summary, "chips": cell.chips, "peaks": peaks,
               "work": mod.work(cell), "units": win["units"]}
        for m in cell.per_layer:
            value = _reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = {"device_ops": tr.top_ops(reduced, window),
                     "idle_gaps": tr.idle_gaps(reduced, window)}
    else:
        values = {"setup_s": stamps["setup_s"],
                  "peak_hbm_gib": device["memory_peak_bytes"] / 2**30, **mod.end_to_end(win)}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}

    # the check: the program's state goes, then the reference runs
    run_.release()
    numbers = run_.numbers(run_.reference())
    checks = {k: {"value": _finite(numbers[k]), "limit": cell.limits[k]} for k in cell.limits}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    attempted = win["units"]
    info = {
        "seed": seed, "setup_s": stamps["setup_s"], "window_s": win["seconds"],
        "units": attempted,
        "compiles": clock.count, "compile_s": clock.seconds,
        "autotune_written": (not autotune_before) and os.path.exists(autotune),
        **{k: v for k, v in numbers.items() if k not in checks},
    }
    if "last_loss" in win:
        info["window_last_loss"] = win["last_loss"]
    print(f"info {json.dumps(info)}", file=err)
    for k, c in checks.items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}", file=err)
    err.flush()
    result = {"correct": correct, "attempted": attempted, "failed": win.get("failed", 0),
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    print(json.dumps(result), file=out, flush=True)
    return result


def _autotune_file() -> str:
    """Where the program's kernel tiling tuner would write: an eager call of a
    kernel wrapper writes it, and later runs would then tile differently."""
    return os.path.join(specs.ROOT, "artifacts", "autotune", "block_m.json")
