"""Kernel microbenches: correctness deltas vs oracle + oracle wall time.

Pallas interpret mode executes the kernel body in Python on CPU, so kernel
wall-clock here is NOT meaningful — correctness is the derived metric and
the XLA oracle time gives the baseline the TPU kernel must beat.
"""

from __future__ import annotations

import os
import sys

# repo root on sys.path so `python benchmarks/kernels_bench.py` works
# standalone (CI) as well as `python -m benchmarks.kernels_bench`
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, emit_json, time_fn
from repro.kernels.attention.ops import flash_sdpa
from repro.kernels.attention.ref import attention_ref
from repro.kernels.coupling.ops import fused_coupling_bwd, fused_coupling_fwd
from repro.kernels.coupling.ref import (
    coupling_bwd_ref,
    coupling_fwd_ref,
    coupling_inv_ref,
)
from repro.kernels.rwkv.ops import rwkv6_wkv
from repro.kernels.rwkv.ref import wkv_ref
from repro.kernels.ssd.ops import mamba2_ssd
from repro.kernels.ssd.ref import ssd_ref

RNG = jax.random.PRNGKey(0)


def run_smoke():
    """CI sanity pass: tiny shapes, flow kernels only, hard-fails on error.

    Interpret-mode Pallas on CPU is slow, so the full ``run()`` is minutes of
    wall clock; this keeps the CI kernel gate to seconds while still
    executing every coupling/flow-step kernel body end-to-end (fwd, bwd,
    inverse).  Kernel bodies are forced (``REPRO_PALLAS_INTERPRET=1``) so the
    wrappers cannot satisfy the parity checks via their CPU reference
    dispatch; the env is restored before the throughput gate, which must
    measure the production path.
    """
    from repro.kernels.common import INTERPRET_ENV

    saved = os.environ.get(INTERPRET_ENV)
    os.environ[INTERPRET_ENV] = "1"
    try:
        _smoke_kernel_bodies()
    finally:
        if saved is None:
            os.environ.pop(INTERPRET_ENV, None)
        else:
            os.environ[INTERPRET_ENV] = saved
    check_flow_training_regression()


def _smoke_kernel_bodies():
    from repro.kernels.coupling.ops import fused_coupling_inv

    x = jax.random.normal(RNG, (2, 64, 4))
    raw = jax.random.normal(jax.random.PRNGKey(1), x.shape)
    t = jax.random.normal(jax.random.PRNGKey(2), x.shape)
    gy = jax.random.normal(jax.random.PRNGKey(3), x.shape)
    gld = jax.random.normal(jax.random.PRNGKey(4), (x.shape[0],))
    y, ld = fused_coupling_fwd(x, raw, t, block_m=64)
    y_ref, ld_ref = coupling_fwd_ref(x, raw, t)
    err = float(jnp.max(jnp.abs(y - y_ref))) + float(jnp.max(jnp.abs(ld - ld_ref)))
    assert err < 1e-4, f"coupling fwd drifted from oracle: {err}"
    emit("smoke/fused_coupling", 0.0, f"max_err_vs_ref={err:.2e}")

    out_k = fused_coupling_bwd(y, raw, t, gy, gld, block_m=64)
    out_ref = coupling_bwd_ref(y, raw, t, gy, gld)
    err = max(
        float(jnp.max(jnp.abs(a - b))) for a, b in zip(out_k, out_ref)
    )
    assert err < 1e-4, f"coupling bwd drifted from oracle: {err}"
    emit("smoke/fused_coupling_bwd", 0.0, f"max_err_vs_ref={err:.2e}")

    x2 = fused_coupling_inv(y, raw, t, block_m=64)
    err = float(jnp.max(jnp.abs(x2 - coupling_inv_ref(y_ref, raw, t))))
    assert err < 1e-4, f"coupling inv drifted from oracle: {err}"
    emit("smoke/fused_coupling_inv", 0.0, f"max_err_vs_ref={err:.2e}")

    from repro.kernels.conv1x1.ops import invertible_conv1x1
    from repro.kernels.conv1x1.ref import conv1x1_mm_ref

    c = 6
    xc = jax.random.normal(RNG, (2, 64, c))
    w = jax.random.normal(jax.random.PRNGKey(5), (c, c))
    err = float(jnp.max(jnp.abs(invertible_conv1x1(xc, w) - conv1x1_mm_ref(xc, w))))
    assert err < 1e-4, f"conv1x1 drifted from oracle: {err}"
    emit("smoke/conv1x1_mm", 0.0, f"max_err_vs_ref={err:.2e}")

    # flow-step megakernel: fused fwd + the two fused backward stages,
    # channel-major (B, C, M)
    from repro.kernels.flowstep.flowstep import coupling_half_bwd, flowstep_fwd, spine_bwd
    from repro.kernels.flowstep.ref import (
        coupling_half_bwd_ref,
        flowstep_fwd_ref,
        spine_bwd_ref,
    )

    ks = jax.random.split(jax.random.PRNGKey(6), 4)
    an_ls = 0.1 * jax.random.normal(ks[0], (c,))
    an_b = 0.1 * jax.random.normal(ks[1], (c,))
    wc = jax.random.normal(ks[2], (c, c)) / jnp.sqrt(c) + jnp.eye(c)
    raw = jax.random.normal(ks[3], (2, 64, c // 2))
    xs = xc.transpose(0, 2, 1)
    h = jnp.concatenate([raw, raw], axis=-1).transpose(0, 2, 1)   # raw; t = raw
    ys, lds = flowstep_fwd(xs, an_ls, an_b, wc, h, block_m=64)
    ys_r, lds_r = flowstep_fwd_ref(xs, an_ls, an_b, wc, h)
    err = float(jnp.max(jnp.abs(ys - ys_r))) + float(jnp.max(jnp.abs(lds - lds_r)))
    assert err < 1e-4, f"flowstep fwd drifted from oracle: {err}"
    emit("smoke/flowstep_fwd", 0.0, f"max_err_vs_ref={err:.2e}")

    gys = jax.random.normal(jax.random.PRNGKey(7), (2, 64, c)).transpose(0, 2, 1)
    glds = jax.random.normal(jax.random.PRNGKey(9), (2,))
    out_k = coupling_half_bwd(ys, h, gys, glds, block_m=64)
    out_r = coupling_half_bwd_ref(ys, h, gys, glds)
    err = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(out_k, out_r))
    assert err < 1e-4, f"flowstep coupling-half bwd drifted from oracle: {err}"
    emit("smoke/flowstep_coupling_half_bwd", 0.0, f"max_err_vs_ref={err:.2e}")

    w_inv = jnp.linalg.inv(wc)
    gxb = gys[:, c // 2:]
    args = (ys, gys, gxb, wc, w_inv, an_ls, an_b)
    out_k = spine_bwd(*args, block_m=64)
    # against the float64 oracle: gW's sums reach several hundred, where
    # the float32 oracle's own rounding is near 1e-4
    with jax.enable_x64(True):
        out_r = spine_bwd_ref(*(jnp.asarray(np.asarray(v, np.float64)) for v in args))
        err = max(float(np.max(np.abs(np.asarray(a, np.float64) - np.asarray(b))))
                  for a, b in zip(out_k, out_r))
    assert err < 1e-4, f"flowstep spine bwd drifted from oracle: {err}"
    emit("smoke/flowstep_spine_bwd", 0.0, f"max_err_vs_ref={err:.2e}")
    print("kernel smoke: OK")


def check_flow_training_regression(threshold: float = 0.15):
    """CI throughput gate: re-measure the coupled training step on the
    production path and fail on a >``threshold`` imgs_per_s regression vs
    the committed ``BENCH_flow_training.json`` — same-backend only (a CPU
    runner cannot gate numbers committed from a TPU host and vice versa).

    Two asserts: (a) the host-invariant structural property — coupled must
    not fall behind the plain-autodiff baseline measured in the same
    interleaved run; (b) a **speed-normalized** comparison to the committed
    coupled number, scaled by this host's ``autodiff_scanned`` control
    (same builder/topology as coupled, so the normalizer is free of the
    cross-host unrolled-vs-scanned swing).  A coupled-only regression trips
    both; a uniformly slower runner trips neither.

    The measured rows are written to ``BENCH_flow_training_gate.json`` so
    every CI run uploads fresh per-run throughput/memory numbers.
    ``REPRO_BENCH_NO_GATE=1`` skips (e.g. while intentionally re-baselining).
    """
    from benchmarks.common import load_gate_baseline
    from benchmarks.flow_training import measure_modes

    committed, reason = load_gate_baseline("flow_training")
    if committed is None:
        print(f"flow-training gate: {reason}")
        return
    rows = measure_modes(("coupled", "autodiff", "autodiff_scanned"), rounds=15)
    got = rows["coupled"]["imgs_per_s"]
    ref = committed["grad_modes"]["coupled"]["imgs_per_s"]
    # host-speed normalizer: the autodiff_scanned control shares coupled's
    # builder/topology, so its ratio to the committed value tracks this
    # host's speed without the cross-builder swing (unrolled-vs-scanned
    # relative cost varies ~20% between same-backend hosts — more than the
    # gate threshold; the plain-autodiff baseline cannot normalize it)
    host_speed = (
        rows["autodiff_scanned"]["imgs_per_s"]
        / committed["grad_modes"]["autodiff_scanned"]["imgs_per_s"]
    )
    ref_scaled = ref * host_speed
    ratio_vs_ad = got / rows["autodiff"]["imgs_per_s"]
    emit(
        "gate/flow_training_coupled", rows["coupled"]["us_per_step"],
        f"imgs_per_s={got:.1f} committed={ref:.1f} host_speed={host_speed:.3f}"
        f" vs_autodiff={ratio_vs_ad:.3f}",
    )
    emit_json(
        "flow_training_gate",
        {
            "workload": committed.get("workload"),
            "backend": jax.default_backend(),
            "grad_modes": rows,
            "committed_coupled_imgs_per_s": ref,
            "host_speed_vs_committed": host_speed,
            "coupled_vs_autodiff": ratio_vs_ad,
        },
    )
    # the structural acceptance property, host-invariant: the fast path must
    # not fall behind the plain-AD baseline measured in the same run
    assert got >= (1.0 - threshold) * rows["autodiff"]["imgs_per_s"], (
        f"coupled-mode fell behind plain autodiff: {got:.1f} vs"
        f" {rows['autodiff']['imgs_per_s']:.1f} imgs/s (allowed -{threshold:.0%})"
    )
    assert got >= (1.0 - threshold) * ref_scaled, (
        f"coupled-mode throughput regressed: {got:.1f} imgs/s vs committed"
        f" {ref:.1f} x host-speed {host_speed:.3f} = {ref_scaled:.1f}"
        f" (allowed -{threshold:.0%})"
    )
    print("flow-training gate: OK")


def run():
    # flash attention
    q = jax.random.normal(RNG, (1, 8, 512, 64), jnp.bfloat16)
    k = jax.random.normal(RNG, (1, 2, 512, 64), jnp.bfloat16)
    v = jax.random.normal(RNG, (1, 2, 512, 64), jnp.bfloat16)
    o = flash_sdpa(q, k, v)
    o_ref = attention_ref(q, k, v)
    err = float(jnp.max(jnp.abs(o.astype(jnp.float32) - o_ref.astype(jnp.float32))))
    us = time_fn(jax.jit(attention_ref), q, k, v)
    emit("kernel/flash_attention", us, f"max_err_vs_ref={err:.2e}")

    # fused coupling
    x = jax.random.normal(RNG, (4, 1024, 8))
    raw = jax.random.normal(jax.random.PRNGKey(1), x.shape)
    t = jax.random.normal(jax.random.PRNGKey(2), x.shape)
    y, ld = fused_coupling_fwd(x, raw, t)
    y_ref, ld_ref = coupling_fwd_ref(x, raw, t)
    err = float(jnp.max(jnp.abs(y - y_ref))) + float(jnp.max(jnp.abs(ld - ld_ref)))
    us = time_fn(jax.jit(coupling_fwd_ref), x, raw, t)
    emit("kernel/fused_coupling", us, f"max_err_vs_ref={err:.2e}")

    # flow-step megakernel: oracle wall time of the three-launch composition
    # the fused forward replaces (actnorm -> conv1x1 -> coupling), in the
    # kernel's channel-major (B, C, M) layout
    from repro.kernels.flowstep.flowstep import flowstep_fwd
    from repro.kernels.flowstep.ref import flowstep_fwd_ref

    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    c = 8
    an_ls = 0.1 * jax.random.normal(ks[0], (c,))
    an_b = 0.1 * jax.random.normal(ks[1], (c,))
    wc = jax.random.normal(ks[2], (c, c)) / jnp.sqrt(c) + jnp.eye(c)
    xs = x.transpose(0, 2, 1)
    h = jnp.concatenate([raw[..., : c // 2], t[..., : c // 2]], axis=-1).transpose(0, 2, 1)
    ys, lds = flowstep_fwd(xs, an_ls, an_b, wc, h)
    ys_r, lds_r = flowstep_fwd_ref(xs, an_ls, an_b, wc, h)
    err = float(jnp.max(jnp.abs(ys - ys_r))) + float(jnp.max(jnp.abs(lds - lds_r)))
    us = time_fn(jax.jit(flowstep_fwd_ref), xs, an_ls, an_b, wc, h)
    emit("kernel/flowstep_fwd", us, f"max_err_vs_ref={err:.2e}")

    # fused coupling backward (reversible VJP; EXPERIMENTS.md §Perf/H1) —
    # the XLA oracle is the generic two-pass baseline the kernel replaces:
    # invert to reconstruct x, then a separate VJP of the forward.
    gy = jax.random.normal(jax.random.PRNGKey(3), x.shape)
    gld = jax.random.normal(jax.random.PRNGKey(4), (x.shape[0],))
    out_k = fused_coupling_bwd(y, raw, t, gy, gld)
    out_ref = coupling_bwd_ref(y, raw, t, gy, gld)
    err = max(
        float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
        for a, b in zip(out_k, out_ref)
    )

    def bwd_oracle(y_, raw_, t_, gy_, gld_):
        x_ = coupling_inv_ref(y_, raw_, t_)
        _, vjp = jax.vjp(coupling_fwd_ref, x_, raw_, t_)
        return (x_,) + vjp((gy_, gld_))

    us = time_fn(jax.jit(bwd_oracle), y, raw, t, gy, gld)
    emit("kernel/fused_coupling_bwd", us, f"max_err_vs_ref={err:.2e}")
    emit_json(
        "coupling_bwd",
        {"kernel": "fused_coupling_bwd", "max_err_vs_ref": err,
         "oracle_us": us, "oracle": "invert_then_vjp(xla)"},
    )

    # ssd
    b, h, s, p, n = 1, 4, 256, 32, 16
    xs = jax.random.normal(RNG, (b, h, s, p))
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(3), (b, h, s)))
    da = -dt * 0.4
    bi = jax.random.normal(jax.random.PRNGKey(4), (b, s, n))
    ci = jax.random.normal(jax.random.PRNGKey(5), (b, s, n))
    yk, stk = mamba2_ssd(xs, da, dt, bi, ci, chunk=64)
    yr, str_ = ssd_ref(xs, da, dt, bi, ci)
    err = float(jnp.max(jnp.abs(yk - yr)))
    us = time_fn(jax.jit(ssd_ref), xs, da, dt, bi, ci)
    emit("kernel/mamba2_ssd", us, f"max_err_vs_ref={err:.2e}")

    # rwkv wkv
    kd = 16
    r = jax.random.normal(RNG, (1, 4, 256, kd))
    kk = jax.random.normal(jax.random.PRNGKey(6), (1, 4, 256, kd))
    vv = jax.random.normal(jax.random.PRNGKey(7), (1, 4, 256, kd))
    w = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(8), (1, 4, 256, kd)))
    u = 0.1 * jax.random.normal(jax.random.PRNGKey(9), (4, kd))
    yk, _ = rwkv6_wkv(r, kk, vv, w, u, chunk=64)
    yr, _ = wkv_ref(r, kk, vv, w, u)
    err = float(jnp.max(jnp.abs(yk - yr)))
    us = time_fn(jax.jit(wkv_ref), r, kk, vv, w, u)
    emit("kernel/rwkv6_wkv", us, f"max_err_vs_ref={err:.2e}")


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--smoke", action="store_true",
        help="fast CI sanity pass (flow kernels only, tiny shapes) + the"
             " flow-training throughput regression gate",
    )
    ap.add_argument(
        "suite", nargs="?", choices=["kernels", "flow_training"],
        default="kernels",
        help="'flow_training' runs the grad-mode training sweep"
             " (throughput + peak memory -> BENCH_flow_training.json)",
    )
    args = ap.parse_args()
    if args.suite == "flow_training":
        from benchmarks.flow_training import run as run_flow_training

        run_flow_training()
    elif args.smoke:
        run_smoke()
    else:
        run()
