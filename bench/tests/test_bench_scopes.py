"""The program's spans and scopes in a trace (``bench.lib.scopes``): the
reduction keeps every existing reading as it was, and the new readers on a
recorded step of glow-fig1 at 256x256x3, batch 8, traced on a TPU v5 lite
with the program's spans and scopes."""

import json
import os
import struct

import pytest

from bench.lib import readers
from bench.lib import scopes as S
from bench.lib import trace as T

DATA = os.path.join(os.path.dirname(__file__), "data")


def _load(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def step_trace():
    return _load("trace_glow_fig1_step.json")


# -- what the benchmark read before the program had spans --------------------

def test_existing_readings_of_the_recorded_step(step_trace):
    """Every existing reading of the recorded step, as the parent read it."""
    from bench.work.glow import flow_kernel_work

    window = T.window_of(step_trace)
    assert window == (43490088.0, 115789658.0)
    summary = T.summarize(step_trace, window)
    assert summary["devices"] == [{"busy_ns": 72196678.0, "class_ns": {
        "other": 37684525.0, "pallas": 16316494.0, "conv": 18156529.0, "container": 39130.0}}]
    model = {"n_scales": 3, "k_steps": 8, "hidden": 64, "channels": 3}
    ops, byts = flow_kernel_work(model, 256, 8, "train")
    ctx = {"summary": summary, "units": 1, "chips": 1,
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "work": {"model_flops": 150e9, "kernel_flops": ops, "kernel_bytes": byts}}
    assert readers.device_idle(ctx) == pytest.approx(0.14231343284613063, rel=1e-12)
    assert readers.mfu(ctx) == pytest.approx(1.0531477846921558, rel=1e-12)
    assert readers.conditioner_ms(ctx) == pytest.approx(18.156529, rel=1e-12)
    assert readers.flow_kernels_ms(ctx) == pytest.approx(16.316494, rel=1e-12)
    assert readers.flow_kernels_roofline(ctx) == pytest.approx(7.250386146146474, rel=1e-12)
    assert T.top_ops(step_trace, window) == [
        ["copy (other)", 0.017505295], ["convolution_add_fusion (conv)", 0.007291544],
        ["coupling_bwd (pallas)", 0.006599501], ["fusion (other)", 0.006013643],
        ["spine_bwd (pallas)", 0.005919794], ["fusion (conv)", 0.005290658],
        ["pad_maximum_fusion (other)", 0.005121874], ["flowstep_fwd (pallas)", 0.003797199],
        ["copy-done (other)", 0.003683166], ["slice-done (other)", 0.003487916]]
    outside = "host: outside the benchmark's spans"
    gaps = [["bench.step", 5.2177e-05], [outside, 2.1047e-05], [outside, 7.621e-06],
            [outside, 1.596e-06], [outside, 9.08e-07], [outside, 8.98e-07],
            [outside, 8.53e-07], ["bench.step", 8.5e-07], [outside, 7.77e-07],
            [outside, 6.99e-07]]
    assert T.idle_gaps(step_trace, window) == gaps
    # a trace without the program's spans: the gaps are named as before
    assert S.idle_gaps(step_trace, window) == gaps
    assert S.idle_gaps(dict(step_trace, spans=[]), window) == gaps


# -- the reduction, on a made-up profile -------------------------------------

class _Obj:
    def __init__(self, **kw):
        self.__dict__.update(kw)


def _ev(start, dur, name, **stats):
    return _Obj(start_ns=start, duration_ns=dur, name=name, stats=list(stats.items()))


KERNEL = ('%spine_bwd.3 = f32[8,64,12]{2,1,0} custom-call(f32[8,64,12]{2,1,0} %a), '
          'custom_call_target="tpu_custom_call"')
FUSION = "%fusion.7 = f32[8,64,12]{2,1,0} fusion(f32[8,64,12]{2,1,0} %a), kind=kLoop"
CONV = ("%convolution.2 = f32[8,8,8,12]{3,2,1,0} convolution(f32[8,8,8,6]{3,2,1,0} %a, "
        "f32[3,3,6,12]{3,2,1,0} %w), window={size=3x3}")
COPY = "%copy.1 = f32[8]{0} copy(f32[8]{0} %a)"
#: each op's name path, as :func:`bench.lib.scopes.op_names` reads it
NAMES = {KERNEL: "jit(step_fn)/while/body/spine_bwd/pallas_call",
         CONV: "jit(step_fn)/while/body/transpose(jvp(conditioner))/conv",
         FUSION: "jit(step_fn)/kernel_layout/reshape", COPY: ""}


def _profile():
    """Two chips whose XLA Ops lines list their ops out of order, and a
    host with the benchmark's and the program's spans."""
    dev = [_Obj(name="XLA Ops", events=[_ev(300, 50, KERNEL), _ev(100, 100, CONV),
                                        _ev(200, 50, FUSION), _ev(400, 10, COPY)]),
           _Obj(name="Steps", events=[_ev(0, 500, "step 1")])]
    host = [_Obj(name="python", events=[
        _ev(50, 500, "bench.loop"),
        _ev(60, 20, "repro.train.dispatch", step_num=7),
        _ev(90, 300, "repro.train.readback", step_num=7),
        _ev(95, 5, "jit_step_fn"),
    ])]
    return _Obj(planes=[_Obj(name="/device:TPU:1", lines=dev),
                        _Obj(name="/device:TPU:0", lines=dev),
                        _Obj(name="/host:CPU", lines=host)])


def test_compact_adds_spans_and_scopes_to_the_trace_form():
    profile = _profile()
    scoped = S.compact(profile, NAMES)
    plain = T.compact(profile)
    assert scoped["host"] == plain["host"] == [[50.0, 500.0, "bench.loop"]]
    assert [d["ops"] for d in scoped["devices"]] == [d["ops"] for d in plain["devices"]]
    assert [d["name"] for d in scoped["devices"]] == ["/device:TPU:0", "/device:TPU:1"]
    d = scoped["devices"][0]
    assert [op[2] for op in d["ops"]] == ["convolution.2", "fusion.7", "spine_bwd.3", "copy.1"]
    assert d["scopes"] == ["jit(step_fn)/while/body/transpose(jvp(conditioner))/conv",
                           "jit(step_fn)/kernel_layout/reshape",
                           "jit(step_fn)/while/body/spine_bwd/pallas_call", ""]
    assert scoped["spans"] == [[60.0, 20.0, "repro.train.dispatch", {"step_num": 7}],
                               [90.0, 300.0, "repro.train.readback", {"step_num": 7}]]


def test_each_op_has_one_owner():
    scoped = S.compact(_profile(), NAMES)
    window = (0.0, 600.0)
    per_device = S.split(scoped, window)
    assert per_device[0] == {"conditioner": 100.0, "kernel_layout": 50.0, "pallas": 50.0,
                             "unscoped": 10.0}
    summary = T.summarize(scoped, window)
    assert sum(per_device[0].values()) == summary["devices"][0]["busy_ns"]
    assert S.innermost_scope("jit(f)/conditioner/jvp(squeeze)/squeeze") == "squeeze"
    assert S.innermost_scope("jit(f)/while/body/squeeze") is None  # a primitive, not a scope
    assert S.innermost_scope("jit(f)/optimizer/transpose(jvp(loss))/mul") == "loss"


def test_gaps_are_named_by_the_span_that_started_last():
    scoped = S.compact(_profile(), NAMES)
    # busy 100-250, 300-350 and 400-410: the gap 50-100 opens inside
    # repro.train.dispatch (60-80), which started after bench.loop; 250-300
    # and 350-400 fall in the read-back; 410-600 in bench.loop alone
    gaps = S.idle_gaps(scoped, (50.0, 600.0))
    assert [g[0] for g in gaps] == ["bench.loop", "repro.train.dispatch",
                                    "repro.train.readback", "repro.train.readback"]
    assert [g[1] for g in gaps] == pytest.approx([190e-9, 50e-9, 50e-9, 50e-9])
    # without the program's spans every one is the benchmark's
    assert [g[0] for g in T.idle_gaps(scoped, (50.0, 600.0))] == ["bench.loop"] * 4



def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*fields) -> bytes:
    """A protobuf message from ``(number, value)``: an int as a varint,
    a float as a double, bytes or str length-delimited."""
    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += _varint(number << 3) + _varint(value)
        elif isinstance(value, float):
            out += _varint(number << 3 | 1) + struct.pack("<d", value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += _varint(number << 3 | 2) + _varint(len(value)) + value
    return out


def _plane(name, events, stat_names):
    """An XPlane: name 2, event metadata 4 and stat metadata 5 (map
    entries: key 1, value 2)."""
    return _msg((2, name),
                *[(4, _msg((1, i), (2, _msg((1, i), *em)))) for i, em in enumerate(events, 1)],
                *[(5, _msg((1, i), (2, _msg((1, i), (2, n))))) for i, n in stat_names.items()])


def test_op_names_read_from_the_serialized_trace():
    """Each op event's name path, from its program's HLO proto in the
    trace's metadata plane (an XSpace made here field by field)."""
    instruction = _msg((1, "fusion.7"), (2, "fusion"),
                       (7, _msg((1, "reshape"), (2, NAMES[FUSION]))))
    module = _msg((1, "jit_step_fn"), (3, _msg((1, "main.1"), (2, instruction),
                                                (2, _msg((1, "copy.1"), (2, "copy"))))))
    metadata = _plane("/host:metadata", [[(2, "jit_step_fn(77)"),
                                          (5, _msg((1, 1), (6, _msg((1, module)))))]],
                      {1: "Hlo Proto"})
    device = _plane("/device:TPU:0", [
        [(2, text), (5, _msg((1, 1), (2, 1.0))), (5, _msg((1, 2), (4, program)))]
        for text, program in ((FUSION, 77), (COPY, 77), (CONV, 99))],
        {1: "Time Scale Multiplier", 2: "program_id"})
    xspace = _msg((1, _plane("/host:CPU", [[(2, "bench.loop")]], {})), (1, metadata), (1, device))
    # the conv's program has no HLO in the trace: it is left out
    assert S.op_names(xspace) == {FUSION: NAMES[FUSION], COPY: ""}


def test_load_dir_reads_a_trace_file(tmp_path):
    """A trace taken here on the CPU: no TPU plane, the spans of both kinds."""
    import jax

    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.loop"):
            with jax.profiler.TraceAnnotation("repro.train.dispatch", step_num=4):
                jax.block_until_ready(jax.numpy.ones(3) * 2)
    trace = S.load_dir(str(tmp_path))
    assert trace["devices"] == []
    assert [h[2] for h in trace["host"]] == ["bench.loop"]
    assert [(s[2], s[3]) for s in trace["spans"]] == [("repro.train.dispatch", {"step_num": 4})]

# -- a recorded step with the program's spans and scopes ---------------------

@pytest.fixture(scope="module")
def scoped_step():
    """Step 25 of a traced glow-fig1.train-256 window (TPU v5 lite), from the
    start of its ``bench.loop`` span to its last device op."""
    return _load("trace_glow_fig1_step_scoped.json")


def test_recorded_scoped_step_by_hand(scoped_step):
    ops, paths = scoped_step["devices"][0]["ops"], scoped_step["devices"][0]["scopes"]
    assert len(ops) == len(paths) == 3640
    window = T.window_of(scoped_step)
    summary = T.summarize(scoped_step, window)
    # by hand: ops that nest nothing (every op but the scans' while loops)
    # count their whole duration, under their innermost scope
    by_hand: dict = {}
    for op, path in zip(ops, paths):
        if op[3] not in T.CONTAINERS:
            key = "pallas" if op[4] == "tpu_custom_call" else S.innermost_scope(path) or "unscoped"
            by_hand[key] = by_hand.get(key, 0.0) + op[1]
    assert by_hand == {"conditioner": 18790296.0, "kernel_layout": 19491396.0,
                       "pallas": 16323673.0, "unscoped": 13590192.0, "squeeze": 1750046.0,
                       "conv1x1": 1635121.0, "loss": 311856.0, "optimizer": 69110.0,
                       "actnorm": 9764.0}
    split = S.split(scoped_step, window)[0]
    containers = summary["devices"][0]["class_ns"]["container"]
    assert split == dict(by_hand, unscoped=by_hand["unscoped"] + containers)
    # nothing counted twice: the owners add up to the busy time
    assert sum(split.values()) == summary["devices"][0]["busy_ns"] == 72024703.0
    # the conditioner's forward and its VJP inside the reversible backward
    assert sum("/transpose(jvp(conditioner))/" in p for p in paths) == 168
    assert sum("/jvp(conditioner)/" in p for p in paths) == 160

    ctx = {"summary": summary, "units": 1, "chips": 1, "trace": scoped_step}
    assert S.optimizer_ms(ctx) == pytest.approx(0.06911)
    assert S.conditioner_scoped_ms(ctx) == pytest.approx(18.790296)
    assert S.kernel_layout_ms(ctx) == pytest.approx(19.491396)
    assert S.unscoped_ms(ctx) == pytest.approx(13.643441)
    assert readers.flow_kernels_ms(ctx) == pytest.approx(16.323673)
    assert S.host_dispatch_ms(ctx) == pytest.approx(2.22909)  # the span, one step
    assert S.host_latents_ms(ctx) is None  # a training step draws no latents


def test_recorded_scoped_step_gaps(scoped_step):
    window = T.window_of(scoped_step)
    # the device waits for the step's launch: the gap opens inside the
    # loop's dispatch span, which started after bench.loop
    assert S.idle_gaps(scoped_step, window)[0] == ["repro.train.dispatch", 0.000666683]
    assert T.idle_gaps(scoped_step, window)[0] == ["bench.loop", 0.000666683]
    spans = {s[2]: s[3] for s in scoped_step["spans"]}
    assert spans["repro.train"] == {"_r": 1, "step_num": 25}
    assert spans["repro.train.dispatch"] == {"step_num": 25}
