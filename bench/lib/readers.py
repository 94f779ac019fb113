"""What the per-layer metrics read from a traced window.

``ctx`` holds the trace's summary (``bench.lib.trace.summarize``), the
number of steps or requests the window finished (``units``), the chips,
the device's peaks, and the work of one step or request
(``model_flops`` over all chips; ``kernel_flops`` and ``kernel_bytes`` of
the flow kernels on one chip).  A reader that finds nothing to read returns
None, and the metric is left out of the result.
"""

from __future__ import annotations


def _window_s(ctx) -> float:
    lo, hi = ctx["summary"]["window"]
    return (hi - lo) / 1e9


def _per_unit_ms(ctx, op_class: str):
    """Self time of one op class per step (or request) and chip, in ms;
    None where the trace holds no such op."""
    devices = ctx["summary"]["devices"]
    if not devices or not ctx["units"] or not any(op_class in d["class_ns"] for d in devices):
        return None
    total = sum(d["class_ns"].get(op_class, 0.0) for d in devices) / len(devices)
    return total / ctx["units"] / 1e6


def device_idle(ctx):
    """Share of the window in which no op ran, averaged over the chips, %."""
    devices = ctx["summary"]["devices"]
    if not devices:
        return None
    busy = sum(d["busy_ns"] for d in devices) / len(devices) / 1e9
    return 100.0 * (1.0 - busy / _window_s(ctx))


def mfu(ctx):
    """The model's operations in the window over what the chips' bf16 peak
    would do in it, %."""
    if not ctx["peaks"] or not ctx["units"]:
        return None
    done = ctx["work"]["model_flops"] * ctx["units"]
    return 100.0 * done / (_window_s(ctx) * ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])


def conditioner_ms(ctx):
    return _per_unit_ms(ctx, "conv")


def flow_kernels_ms(ctx):
    return _per_unit_ms(ctx, "pallas")


def flow_kernels_roofline(ctx):
    """The flow steps' least time (memory-bound here: bytes over the HBM
    bandwidth exceed operations over the peak) over the Pallas kernels'
    time, %."""
    kernel_ms = _per_unit_ms(ctx, "pallas")
    if not ctx["peaks"] or not kernel_ms:
        return None
    w, p = ctx["work"], ctx["peaks"]
    least_s = max(w["kernel_flops"] / p["bf16_flops_per_s"],
                  w["kernel_bytes"] / p["hbm_bytes_per_s"])
    return 100.0 * least_s / (kernel_ms / 1e3)
