"""Profiler traces: capture, reduce to a compact form, and read.

``compact`` keeps, for each TPU device plane, the events of its "XLA Ops"
line as ``[start_ns, dur_ns, name, opcode, detail]`` (``detail`` is the
fusion kind or the custom-call target), and the benchmark's own host spans
(``bench.*``) as ``[start_ns, dur_ns, name]``.  Device and host events share
the profiler's clock.  Every reduction below works on that compact form, so
a recorded trace can be checked by hand and in a test.

XLA Ops events nest (a ``while`` spans the ops of its body), so op time is
self time: an event's duration less that of the events it encloses.
"""

from __future__ import annotations

import glob
import os
import re

_KIND = re.compile(r"kind=(k[A-Za-z]+)")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
#: opcodes of collectives, in their synchronous and asynchronous forms
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
CONTAINERS = ("while", "conditional", "call")


def parse_op(text: str):
    """``(name, opcode, detail)`` from an event's HLO instruction text."""
    name = text.split(" = ", 1)[0].lstrip("%").strip()
    rest = text.split(" = ", 1)[1] if " = " in text else ""
    # the opcode follows the result shape: the first "word(" outside it
    m = None
    depth = 0
    for i, ch in enumerate(rest):
        if ch in "([{":
            if ch == "(" and depth == 0 and i > 0 and (rest[i - 1].isalnum() or rest[i - 1] == "-"):
                j = i - 1
                while j >= 0 and (rest[j].isalnum() or rest[j] in "-_"):
                    j -= 1
                m = rest[j + 1:i]
                break
            depth += 1
        elif ch in ")]}":
            depth -= 1
    opcode = m or ""
    detail = ""
    if opcode == "fusion":
        k = _KIND.search(rest)
        detail = k.group(1) if k else ""
    elif opcode == "custom-call":
        t = _TARGET.search(rest)
        detail = t.group(1) if t else ""
    return name, opcode, detail


def compact(profile_data, host_prefix: str = "bench.") -> dict:
    """The compact trace of a ``jax.profiler.ProfileData``."""
    devices, host = [], []
    for plane in profile_data.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            ops = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    name, opcode, detail = parse_op(ev.name)
                    ops.append([float(ev.start_ns), float(ev.duration_ns), name, opcode, detail])
            ops.sort(key=lambda e: (e[0], -e[1]))
            devices.append({"name": plane.name, "ops": ops})
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(host_prefix):
                        host.append([float(ev.start_ns), float(ev.duration_ns), ev.name])
    devices.sort(key=lambda d: int(d["name"].rsplit(":", 1)[1]))
    host.sort()
    return {"devices": devices, "host": host}


def load_dir(logdir: str) -> dict:
    import jax

    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {logdir}, found {len(paths)}")
    return compact(jax.profiler.ProfileData.from_file(paths[0]))


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------


def op_class(op) -> str:
    """What an op is: a Pallas kernel, an XLA conv or matmul (an output
    fusion or a bare convolution/dot), a collective, a loop container, or
    other work (copies, elementwise and reduction fusions, slices)."""
    _, _, name, opcode, detail = op
    if opcode == "custom-call" and detail == "tpu_custom_call":
        return "pallas"
    base = opcode.removesuffix("-start").removesuffix("-done")
    if base in COLLECTIVES or any(c in name for c in COLLECTIVES):
        return "collective"
    if opcode in CONTAINERS:
        return "container"
    if opcode in ("convolution", "dot") or (opcode == "fusion" and detail == "kOutput"):
        return "conv"
    return "other"


def self_times(ops, window):
    """``[(op, self_ns)]`` for the ops inside ``window``: each op's time
    clipped to the window, less the time of the ops nested in it."""
    lo, hi = window
    out = []
    stack = []  # (end, index into out)
    for op in ops:
        start, dur = op[0], op[1]
        end = start + dur
        if end <= lo or start >= hi:
            continue
        s, e = max(start, lo), min(end, hi)
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack:  # nested: the parent loses this op's time
            parent = stack[-1][1]
            out[parent][1] -= e - s
        out.append([op, e - s])
        stack.append((end, len(out) - 1))
    return [(op, max(t, 0.0)) for op, t in out]


def busy_intervals(ops, window):
    """Union of the op intervals inside ``window``, as sorted pairs."""
    lo, hi = window
    merged = []
    for op in ops:
        s, e = max(op[0], lo), min(op[0] + op[1], hi)
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def window_of(trace) -> tuple[float, float]:
    """From the first host span of the benchmark to the last device op."""
    if not trace["host"]:
        raise ValueError("the trace holds no bench.* host span")
    start = trace["host"][0][0]
    end = max(op[0] + op[1] for d in trace["devices"] for op in d["ops"])
    return start, end


def summarize(trace, window=None) -> dict:
    """Per-device busy time and self time by op class, over ``window``."""
    window = window or window_of(trace)
    per_device = []
    for d in trace["devices"]:
        classes: dict = {}
        for op, t in self_times(d["ops"], window):
            c = op_class(op)
            classes[c] = classes.get(c, 0.0) + t
        busy = sum(e - s for s, e in busy_intervals(d["ops"], window))
        per_device.append({"busy_ns": busy, "class_ns": classes})
    return {"window": window, "devices": per_device}


def _group(name: str) -> str:
    """An op's name without its instance number: ``fusion.960`` -> ``fusion``."""
    return re.sub(r"(\.\d+)+$", "", name)


def top_ops(trace, window, n: int = 10):
    """The ``n`` op groups with the most self time on the first device,
    as ``[name, seconds]``."""
    totals: dict = {}
    for op, t in self_times(trace["devices"][0]["ops"], window):
        key = f"{_group(op[2])} ({op_class(op)})"
        totals[key] = totals.get(key, 0.0) + t
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in ranked]


def idle_gaps(trace, window, n: int = 10):
    """The ``n`` longest idle gaps of the first device, each named by the
    host span that was open at its middle, as ``[name, seconds]``."""
    busy = busy_intervals(trace["devices"][0]["ops"], window)
    gaps, prev = [], window[0]
    for s, e in busy:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    if window[1] > prev:
        gaps.append((prev, window[1]))
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) / 2
        open_spans = [h for h in trace["host"] if h[0] <= mid <= h[0] + h[1]]
        label = open_spans[-1][2] if open_spans else "host: outside the benchmark's spans"
        out.append([label, (e - s) / 1e9])
    return out
