"""The program's own names in a profiler trace, and the readers of them.

``compact`` is :func:`bench.lib.trace.compact` with two keys more: ``spans``,
the program's host spans (``repro.*``) as ``[start_ns, dur_ns, name, args]``
(``args`` the span's keyword arguments, such as ``step_num`` or
``request``), and on each device ``scopes``, aligned index by index with
``ops``: the op's name path, the scopes it was traced under and then its
primitive (``jit(step_fn)/while/body/transpose(jvp(conditioner))/conv...``),
or "" where it has none (ops that XLA inserts, such as layout copies).
``host``, the five fields of every op and their order are those of
``bench.lib.trace``, so every reduction there reads this form too.

A TPU trace does not put the name path on the op's event: it keeps each
program's optimized HLO (``/host:metadata``, one ``Hlo Proto`` per
program), whose instructions carry it as ``op_name``, and tags each op's
event metadata with its ``program_id``.  ``jax.profiler.ProfileData`` shows
neither, so :func:`op_names` reads them from the serialized trace with a
small reader of the protobuf wire format.

An op belongs to its innermost program scope (:data:`SCOPES`), found in its
path with the transformation wrappers (``jvp(...)``, ``transpose(...)``)
taken off; a Pallas kernel belongs to the flow kernels whatever its scope.
So every op is counted once: in a kernel, in one scope, or in none.
"""

from __future__ import annotations

import glob
import os
import re

from bench.lib import trace as tr

#: the program's device scopes (``jax.named_scope`` in ``src/repro``)
SCOPES = ("conditioner", "kernel_layout", "optimizer", "actnorm", "conv1x1",
          "squeeze", "loss", "grad_reduce")
#: the prefix of the program's host spans
SPAN_PREFIX = "repro."
_WRAPPER = re.compile(r"^(\w+\()+|\)+$")
_PROGRAM = re.compile(r"\((\d+)\)$")


def _varint(b: bytes, i: int):
    r = shift = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return r, i


def _fields(b: bytes):
    """``(field number, value)`` of one protobuf message: an int for a
    varint, bytes for anything length-delimited or fixed-width."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire in (1, 5):
            width = 8 if wire == 1 else 4
            v, i = b[i:i + width], i + width
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _first(b: bytes, number: int, default=b""):
    return next((v for f, v in _fields(b) if f == number), default)


def _metadata(plane: bytes):
    """A plane's name, its event metadata (XEventMetadata: name, stats) and
    its stat names by id."""
    name, events, stat_names = "", [], {}
    for f, v in _fields(plane):
        if f == 2:
            name = v.decode()
        elif f in (4, 5):  # map entries: key 1, value 2
            value = _first(v, 2)
            if f == 4:
                events.append(value)
            else:
                stat_names[_first(value, 1, 0)] = _first(value, 2).decode()
    return name, events, stat_names


def _stats(event_metadata: bytes, stat_names: dict) -> dict:
    """XStat: metadata id 1; value uint64 3, int64 4, str 5 or bytes 6."""
    out = {}
    for f, stat in _fields(event_metadata):
        if f == 5:
            fs = dict(_fields(stat))
            value = next((fs[k] for k in (3, 4, 5, 6) if k in fs), None)
            out[stat_names.get(fs.get(1), "")] = value
    return out


def _instruction_op_names(hlo_proto: bytes) -> dict:
    """``{instruction: op_name}`` of the HloModuleProto in an HloProto
    (module 1 > computations 3 > instructions 2 > name 1, metadata 7 >
    op_name 2)."""
    out = {}
    for f, computation in _fields(_first(hlo_proto, 1)):
        if f != 3:
            continue
        for g, instruction in _fields(computation):
            if g == 2:
                fs = dict(_fields(instruction))
                out[fs.get(1, b"").decode()] = _first(fs.get(7, b""), 2).decode()
    return out


def op_names(xspace: bytes) -> dict:
    """``{HLO text of an XLA op event: its op_name}`` from a serialized
    XSpace (planes 1; a plane's name 2, event metadata 4, stat metadata 5)."""
    programs, device_events = {}, []
    for f, plane in _fields(xspace):
        if f != 1:
            continue
        name, events, stat_names = _metadata(plane)
        if name == "/host:metadata":
            for em in events:
                m = _PROGRAM.search(_first(em, 2).decode())
                proto = _stats(em, stat_names).get("Hlo Proto")
                if m and proto:
                    programs[int(m.group(1))] = _instruction_op_names(proto)
        elif re.fullmatch(r"/device:TPU:\d+", name):
            device_events += [(_first(em, 2).decode(), _stats(em, stat_names).get("program_id"))
                              for em in events]
    out = {}
    for text, program in device_events:
        names = programs.get(program)
        if names is not None:
            out[text] = names.get(tr.parse_op(text)[0], "")
    return out


def compact(profile_data, names: dict | None = None) -> dict:
    """The compact trace of a ``jax.profiler.ProfileData``, with spans, and
    with scopes from ``names`` (:func:`op_names` of the same trace)."""
    names = names or {}
    out = tr.compact(profile_data)
    scoped, spans = [], []
    for plane in profile_data.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            pairs = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    name, opcode, detail = tr.parse_op(ev.name)
                    op = [float(ev.start_ns), float(ev.duration_ns), name, opcode, detail]
                    pairs.append((op, names.get(ev.name, "")))
            # the order of bench.lib.trace.compact (a stable sort on the same key)
            pairs.sort(key=lambda p: (p[0][0], -p[0][1]))
            scoped.append((int(plane.name.rsplit(":", 1)[1]), [s for _, s in pairs]))
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        args = {k: v for k, v in ev.stats if isinstance(v, (int, float, str))}
                        spans.append([float(ev.start_ns), float(ev.duration_ns), ev.name, args])
    scoped.sort()
    for d, (_, scopes) in zip(out["devices"], scoped):
        d["scopes"] = scopes
    spans.sort(key=lambda s: (s[0], s[1]))
    out["spans"] = spans
    return out


def load_dir(logdir: str) -> dict:
    import jax

    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {logdir}, found {len(paths)}")
    with open(paths[0], "rb") as f:
        xspace = f.read()
    return compact(jax.profiler.ProfileData.from_serialized_xspace(xspace), op_names(xspace))


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------


def innermost_scope(path: str) -> str | None:
    """The innermost program scope in an op's name path, or None.  The last
    part of the path is the op's primitive, never a scope (``squeeze`` is
    both a scope and a primitive)."""
    for part in reversed(path.split("/")[:-1]):
        bare = _WRAPPER.sub("", part)
        if bare in SCOPES:
            return bare
    return None


def owner(op, path: str) -> str:
    """Who an op's time belongs to: ``pallas`` for a Pallas kernel, else its
    innermost program scope, else ``unscoped``."""
    if tr.op_class(op) == "pallas":
        return "pallas"
    return innermost_scope(path) or "unscoped"


def split(trace, window) -> list:
    """Per device, the self time (ns) of the ops inside ``window`` by owner."""
    out = []
    for d in trace["devices"]:
        path = {id(op): s for op, s in zip(d["ops"], d["scopes"])}
        owners: dict = {}
        for op, t in tr.self_times(d["ops"], window):
            k = owner(op, path[id(op)])
            owners[k] = owners.get(k, 0.0) + t
        out.append(owners)
    return out


def idle_gaps(trace, window, n: int = 10):
    """:func:`bench.lib.trace.idle_gaps` with the program's spans among the
    host spans: each gap is named by the open span, of ``bench.*`` and
    ``repro.*`` together, that started last."""
    host = sorted(trace["host"] + [s[:3] for s in trace.get("spans", [])])
    return tr.idle_gaps({"devices": trace["devices"], "host": host}, window, n)


# ---------------------------------------------------------------------------
# readers: ``ctx`` as in bench.lib.readers, with the compact trace of this
# module under ``ctx["trace"]``
# ---------------------------------------------------------------------------


def _owner_ms(ctx, key: str):
    """Self time of one owner per step (or request) and chip, in ms; None
    where the trace names no scope at all."""
    trace = ctx.get("trace")
    if not trace or not ctx["units"] or not any(any(d.get("scopes", ())) for d in
                                                trace["devices"]):
        return None
    per_device = split(trace, ctx["summary"]["window"])
    return sum(d.get(key, 0.0) for d in per_device) / len(per_device) / ctx["units"] / 1e6


def _span_ms(ctx, name: str):
    """Host time in the spans called ``name`` per step (or request), each
    clipped to the window, in ms; None where the trace holds none."""
    trace = ctx.get("trace")
    lo, hi = ctx["summary"]["window"]
    spans = [s for s in (trace or {}).get("spans", []) if s[2] == name]
    if not spans or not ctx["units"]:
        return None
    total = sum(max(0.0, min(s[0] + s[1], hi) - max(s[0], lo)) for s in spans)
    return total / ctx["units"] / 1e6


def host_dispatch_ms(ctx):
    return _span_ms(ctx, "repro.train.dispatch")


def host_latents_ms(ctx):
    return _span_ms(ctx, "repro.serve.latents")


def optimizer_ms(ctx):
    return _owner_ms(ctx, "optimizer")


def conditioner_scoped_ms(ctx):
    return _owner_ms(ctx, "conditioner")


def kernel_layout_ms(ctx):
    return _owner_ms(ctx, "kernel_layout")


def unscoped_ms(ctx):
    return _owner_ms(ctx, "unscoped")
