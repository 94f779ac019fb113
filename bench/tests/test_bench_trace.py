"""The trace reduction, on one recorded training step of glow-fig1 at
256x256x3, batch 8 (a TPU v5 lite trace, cut to the ops of one step)."""

import json
import os

import pytest

from bench.lib import readers
from bench.lib import trace as T

DATA = os.path.join(os.path.dirname(__file__), "data")


@pytest.fixture(scope="module")
def step_trace():
    with open(os.path.join(DATA, "trace_glow_fig1_step.json")) as f:
        return json.load(f)


def test_parse_op_reads_opcode_kind_and_target():
    kernel = ('%coupling_bwd.29 = (f32[8,16384,6]{2,1,0:T(8,128)}, f32[8,16384,6]{2,1,0:T(8,128)}) '
              'custom-call(f32[8,16384,6]{2,1,0:T(8,128)} %bitcast.955), '
              'custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}')
    assert T.parse_op(kernel) == ("coupling_bwd.29", "custom-call", "tpu_custom_call")
    conv = ('%convolution_add_fusion.29 = f32[8,128,128,12]{3,0,2,1:T(8,128)S(1)} '
            'fusion(f32[12]{0:T(128)S(1)} %fusion.989), kind=kOutput, calls=%fused_computation.271')
    assert T.parse_op(conv) == ("convolution_add_fusion.29", "fusion", "kOutput")
    loop = ('%while.17 = (s32[]{:T(128)}, f32[8,128,128,12]{3,2,1,0:T(8,128)S(1)}) '
            'while((s32[]{:T(128)}, f32[8,128,128,12]{3,2,1,0:T(8,128)S(1)}) %tuple), body=%b')
    assert T.parse_op(loop)[1] == "while"
    assert T.parse_op("%all-reduce.3 = f32[12]{0} all-reduce(f32[12]{0} %x), to_apply=%add")[1] \
        == "all-reduce"


def test_self_time_subtracts_nested_ops():
    ops = [[0.0, 100.0, "while.1", "while", ""],
           [10.0, 20.0, "fusion.1", "fusion", "kOutput"],
           [40.0, 30.0, "coupling_bwd.1", "custom-call", "tpu_custom_call"],
           [150.0, 10.0, "copy.1", "copy", ""]]
    times = {op[2]: t for op, t in T.self_times(ops, (0.0, 200.0))}
    assert times == {"while.1": 50.0, "fusion.1": 20.0, "coupling_bwd.1": 30.0, "copy.1": 10.0}
    assert T.busy_intervals(ops, (0.0, 200.0)) == [[0.0, 100.0], [150.0, 160.0]]
    assert [round(g[1] * 1e9) for g in T.idle_gaps({"devices": [{"ops": ops}], "host": []},
                                                   (0.0, 200.0))] == [50, 40]


def test_recorded_step_by_hand(step_trace):
    ops = step_trace["devices"][0]["ops"]
    window = T.window_of(step_trace)
    summary = T.summarize(step_trace, window)
    classes = summary["devices"][0]["class_ns"]
    # Pallas kernels and output fusions nest nothing: their self time is
    # their duration, summed by hand here
    pallas = sum(o[1] for o in ops if o[3] == "custom-call" and o[4] == "tpu_custom_call")
    conv = sum(o[1] for o in ops if o[3] == "fusion" and o[4] == "kOutput")
    assert classes["pallas"] == pytest.approx(pallas)
    assert classes["conv"] == pytest.approx(conv)
    assert pallas / 1e6 == pytest.approx(16.3165, abs=1e-3)
    # every op's self time adds up to the busy time: nothing counted twice
    assert sum(classes.values()) == pytest.approx(summary["devices"][0]["busy_ns"])
    assert (window[1] - window[0]) / 1e6 == pytest.approx(72.29957, abs=1e-4)
    names = {o[2].split(".")[0] for o in ops if o[4] == "tpu_custom_call"}
    assert names == {"flowstep_fwd", "coupling_bwd", "spine_bwd"}


def test_readers_on_recorded_step(step_trace):
    from bench.work.glow import flow_kernel_work

    model = {"n_scales": 3, "k_steps": 8, "hidden": 64, "channels": 3}
    ops, byts = flow_kernel_work(model, 256, 8, "train")
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = {"summary": T.summarize(step_trace), "units": 1, "chips": 1, "peaks": peaks,
           "work": {"model_flops": 150e9, "kernel_flops": ops, "kernel_bytes": byts}}
    kernel_ms = readers.flow_kernels_ms(ctx)
    assert kernel_ms == pytest.approx(16.3165, abs=1e-3)
    # memory-bound: 968.9 MB over 819 GB/s, over the kernels' 16.3 ms
    assert readers.flow_kernels_roofline(ctx) == pytest.approx(
        100 * byts / 819e9 / (kernel_ms / 1e3))
    assert 0 < readers.flow_kernels_roofline(ctx) < 100
    assert 0 <= readers.device_idle(ctx) < 1
    assert readers.mfu(ctx) == pytest.approx(100 * 150e9 / (0.07229957 * 197e12), rel=1e-4)
    assert readers._per_unit_ms(ctx, "collective") is None  # one chip: nothing to read
