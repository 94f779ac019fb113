#!/usr/bin/env python3
"""On-chip smoke test of the main path: scanned-GLOW flow training on a TPU.

    python3 chip_smoke.py                # one chip
    python3 chip_smoke.py --four-chips   # data-parallel path on four chips

One chip: GLOW_SCANNED at the paper's widths (3 scales x 8 steps, hidden
64, coupled reversible backward) on 256x256 RGB, batch 8, through the
normal entry points (``train_flow``, ``flow.inverse``), with every Pallas
kernel compiled.  Phases:

  a. a TPU is present and the kernels take the compiled path;
  b. each hot-path kernel at the step's shapes matches its jnp oracle
     (float32, precision HIGHEST);
  c. five training steps: no restart, finite losses, Pallas kernels in the
     step's HLO, and the step loss equal to the oracle-path loss;
  d. sampling by inversion: forward(inverse(z)) recovers z.

``--four-chips`` runs only the data-parallel train step on a (4, 1) data
mesh against the single-device step (loss and gradients), and batch-sharded
sampling through ``FlowServeEngine`` against single-device samples.

Every failure raises, so the exit code is non-zero; the last stdout line is
``{"ok": true, "device": {...}}`` only when every phase passed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

SIZE, BATCH, STEPS = 256, 8, 5
#: kernel vs oracle: max |kernel - oracle| / max(1, max |oracle|)
KERNEL_TOL = 1e-4
#: step loss vs the same params through the oracles, relative
LOSS_TOL = 1e-4
#: forward(inverse(z)) vs z: max |diff| / max(1, max |z|).  Drift through 24
#: steps reaches ~1e-4 in f32 on a CPU; on the chip the conditioner convs
#: also run at the default (bf16-pass) precision
ROUNDTRIP_TOL = 1e-2
#: data-parallel vs single-device loss, gradients and samples (the bound of
#: tests/test_dist_flows.py): |a - b| <= tol * (1 + |b|)
DP_TOL = 1e-4


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str):
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str):
    print(msg, flush=True)


def rel_err(a, ref) -> float:
    import numpy as np

    a, ref = np.asarray(a, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.abs(a - ref)) / max(1.0, float(np.max(np.abs(ref)))))


def require_chip(n_chips: int):
    """Phase a: a TPU, the compiled kernel path, no interpret override."""
    from repro.kernels.common import INTERPRET_ENV

    check(INTERPRET_ENV not in os.environ,
          f"{INTERPRET_ENV} is set; the smoke test runs compiled kernels only")
    import jax

    devices = jax.devices()
    check(devices[0].platform == "tpu",
          f"no TPU: JAX found {devices[0].platform} devices")
    check(len(devices) == n_chips,
          f"expected {n_chips} TPU chip(s), found {len(devices)}")
    from repro.kernels.common import kernel_path, resolve_interpret
    from repro.utils.cache import enable_compile_cache

    check(kernel_path() == "compiled", f"kernel path is {kernel_path()}")
    check(resolve_interpret() is False, "Pallas interpret mode is on")
    log(f"device: {devices[0].device_kind} x{len(devices)}; kernel path: "
        f"{kernel_path()}; compile cache: {enable_compile_cache()}")
    return devices


def step_shapes():
    """(M, C) of each scale's flow steps for a SIZE x SIZE RGB image: the
    Haar squeeze quarters M and quadruples C, the split halves C."""
    shapes, m, c = [], SIZE * SIZE, 3
    for _ in range(3):
        m, c = m // 4, c * 4
        shapes.append((m, c))
        c //= 2
    return shapes


def kernel_parity():
    """Phase b: every hot-path kernel vs its oracle at the step's shapes."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.common import pick_block_m
    from repro.kernels.conv1x1.conv1x1 import conv1x1_gw, conv1x1_mm
    from repro.kernels.conv1x1.ref import conv1x1_gw_ref, conv1x1_mm_ref
    from repro.kernels.coupling.coupling import coupling_bwd, coupling_fwd, coupling_inv
    from repro.kernels.coupling.ref import coupling_bwd_ref, coupling_fwd_ref, coupling_inv_ref
    from repro.kernels.flowstep.flowstep import (
        coupling_half_bwd,
        flowstep_fwd,
        flowstep_inv,
        spine_bwd,
    )
    from repro.kernels.flowstep.ref import (
        coupling_half_bwd_ref,
        flowstep_fwd_ref,
        flowstep_inv_ref,
        spine_bwd_ref,
    )

    worst = 0.0
    for m, c in step_shapes():
        ca = c // 2
        ks = jax.random.split(jax.random.PRNGKey(m + c), 10)
        x = jax.random.normal(ks[0], (BATCH, m, c))        # full-width tiles
        g = jax.random.normal(ks[1], (BATCH, m, c))
        xa = jax.random.normal(ks[2], (BATCH, m, ca))      # transformed half
        ga = jax.random.normal(ks[3], (BATCH, m, ca))
        raw = jax.random.normal(ks[4], (BATCH, m, ca))
        t = jax.random.normal(ks[5], (BATCH, m, ca))
        gld = jax.random.normal(ks[6], (BATCH,))
        an_ls = 0.1 * jax.random.normal(ks[7], (c,))
        an_b = 0.1 * jax.random.normal(ks[8], (c,))
        w = jax.random.normal(ks[9], (c, c)) / jnp.sqrt(c) + jnp.eye(c)
        w_inv = jnp.linalg.inv(w)
        bm = pick_block_m(m)
        kw = dict(block_m=bm, interpret=False)
        # the flow-step kernels are channel-major (B, C, M), at their own block
        xc, gc = x.transpose(0, 2, 1), g.transpose(0, 2, 1)
        h = jnp.concatenate([raw, t], axis=-1).transpose(0, 2, 1)
        gxb = ga.transpose(0, 2, 1)[:, : c - ca]
        ckw = dict(interpret=False)
        cases = [
            ("flowstep_fwd", flowstep_fwd(xc, an_ls, an_b, w, h, **ckw),
             flowstep_fwd_ref(xc, an_ls, an_b, w, h)),
            ("flowstep_inv", flowstep_inv(xc, an_ls, an_b, w_inv, h, **ckw),
             flowstep_inv_ref(xc, an_ls, an_b, w_inv, h)),
            ("coupling_half_bwd", coupling_half_bwd(xc, h, gc, gld, **ckw),
             coupling_half_bwd_ref(xc, h, gc, gld)),
            ("spine_bwd", spine_bwd(xc, gc, gxb, w, w_inv, an_ls, an_b, **ckw),
             spine_bwd_ref(xc, gc, gxb, w, w_inv, an_ls, an_b)),
            ("coupling_fwd", coupling_fwd(xa, raw, t, **kw),
             coupling_fwd_ref(xa, raw, t)),
            ("coupling_bwd", coupling_bwd(xa, raw, t, ga, gld, **kw),
             coupling_bwd_ref(xa, raw, t, ga, gld)),
            ("coupling_inv", coupling_inv(xa, raw, t, **kw),
             coupling_inv_ref(xa, raw, t)),
            ("conv1x1_mm", conv1x1_mm(x, w, **kw), conv1x1_mm_ref(x, w)),
            ("conv1x1_gw", conv1x1_gw(x, g, **kw), conv1x1_gw_ref(x, g)),
        ]
        for name, out, ref in cases:
            outs, refs = jax.tree_util.tree_leaves(out), jax.tree_util.tree_leaves(ref)
            check(len(outs) == len(refs), f"{name}: {len(outs)} outputs vs {len(refs)}")
            err = max(rel_err(a, r) for a, r in zip(outs, refs))
            worst = max(worst, err)
            log(f"  kernel {name:13s} M={m:6d} C={c:3d} block_m={bm:4d}: "
                f"max rel err {err:.3e} (tol {KERNEL_TOL:.0e})")
            check(err <= KERNEL_TOL, f"{name} at M={m}, C={c}: error {err:.3e}")
    return worst


def one_chip():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench.lib.harness import CompileClock
    from repro.config import TrainConfig
    from repro.configs.flows import GLOW_SCANNED, build_flow
    from repro.core.distributions import std_normal_sample
    from repro.core.glow_scan import resolve_coupled_bwd
    from repro.core.objectives import nll_loss
    from repro.data import SyntheticImages
    from repro.kernels.common import reference_kernels
    from repro.train import train_flow

    clock = CompileClock()
    log("phase b: kernel parity at the step's shapes")
    kernel_worst = kernel_parity()

    log(f"phase c: {STEPS} train_flow steps of {GLOW_SCANNED.name} at "
        f"{SIZE}x{SIZE}x3, batch {BATCH}")
    flow = build_flow(GLOW_SCANNED)
    strategy = resolve_coupled_bwd("auto")
    data = SyntheticImages(size=SIZE, batch=BATCH, seed=0)
    x0 = data.batch_at(0)
    with tempfile.TemporaryDirectory() as ckpt_dir:
        # lr 1e-4: from 64x64 up, this flow on these images diverges once
        # the rate passes ~5e-4, on the CPU as on the chip
        cfg = TrainConfig(steps=STEPS, lr=1e-4, warmup_steps=1,
                          checkpoint_every=STEPS, checkpoint_dir=ckpt_dir,
                          max_restarts=0)
        clock.phase = "train"
        t0 = time.perf_counter()
        res = train_flow(flow, data, cfg, x0)
        train_wall = time.perf_counter() - t0
        clock.phase = "after"
    compile_s = clock.seconds.get("train", 0.0)
    check(res.restarts == 0, f"training restarted {res.restarts} time(s)")
    check(len(res.losses) == STEPS, f"{len(res.losses)} losses for {STEPS} steps")
    check(all(np.isfinite(res.losses)), f"non-finite loss: {res.losses}")
    log(f"  losses: {res.losses}")

    state = {"params": res.params, "opt": res.opt_state,
             "err": jax.tree_util.tree_map(lambda _: None, res.params)}
    hlo = res.step_fn.lower(state, x0, jnp.asarray(STEPS, jnp.int32)).compile().as_text()
    n_kernels = hlo.count("tpu_custom_call")
    check(n_kernels > 0, "no tpu_custom_call in the compiled train step")
    log(f"  compiled step holds {n_kernels} tpu_custom_call sites")

    # steady-state step time: the loop's own jitted step, fed back its state
    times = []
    for i in range(STEPS):
        t0 = time.perf_counter()
        state, metrics = res.step_fn(state, x0, jnp.asarray(STEPS + i, jnp.int32))
        jax.block_until_ready((state, metrics))
        times.append(time.perf_counter() - t0)
    check(bool(np.isfinite(float(metrics["loss"]))), "non-finite loss after timing")
    step_s = float(np.median(times))

    # the step loss at the initial params vs the same params through the oracles
    params0 = flow.init(jax.random.PRNGKey(cfg.seed), x0)
    with reference_kernels():
        oracle_loss = jax.jit(lambda p, x: nll_loss(flow, p, x)).lower(params0, x0).compile()
    check("tpu_custom_call" not in oracle_loss.as_text(),
          "the oracle loss calls a Pallas kernel")
    oracle0 = float(oracle_loss(params0, x0))
    err0 = abs(res.losses[0] - oracle0) / max(1.0, abs(oracle0))
    log(f"  step-0 loss {res.losses[0]:.7f} vs oracle {oracle0:.7f}: "
        f"rel err {err0:.3e} (tol {LOSS_TOL:.0e})")
    check(err0 <= LOSS_TOL, f"step loss differs from the oracle loss by {err0:.3e}")
    # ...and at trained params, where the conditioners are no longer zero
    params = state["params"]
    kernel_loss = float(jax.jit(lambda p, x: nll_loss(flow, p, x))(params, x0))
    oracle = float(oracle_loss(params, x0))
    err1 = abs(kernel_loss - oracle) / max(1.0, abs(oracle))
    log(f"  trained loss {kernel_loss:.7f} vs oracle {oracle:.7f}: "
        f"rel err {err1:.3e} (tol {LOSS_TOL:.0e})")
    check(err1 <= LOSS_TOL, f"kernel loss differs from the oracle loss by {err1:.3e}")

    log("phase d: sampling by inversion")
    z_like = jax.eval_shape(lambda p, x: flow.forward(p, x)[0], params, x0)
    z = std_normal_sample(jax.random.PRNGKey(1), z_like)
    x_s = jax.jit(flow.inverse)(params, z)
    z_back = jax.jit(lambda p, x: flow.forward(p, x)[0])(params, x_s)
    check(all(bool(jnp.all(jnp.isfinite(v))) for v in jax.tree_util.tree_leaves(x_s)),
          "non-finite samples")
    err_rt = max(rel_err(a, b) for a, b in zip(jax.tree_util.tree_leaves(z_back),
                                               jax.tree_util.tree_leaves(z)))
    log(f"  forward(inverse(z)) vs z: rel err {err_rt:.3e} (tol {ROUNDTRIP_TOL:.0e})")
    check(err_rt <= ROUNDTRIP_TOL, f"round trip error {err_rt:.3e}")

    log(f"summary: backward strategy {strategy}; compile {compile_s:.1f}s in "
        f"train_flow ({train_wall:.1f}s wall for {STEPS} steps); step "
        f"{step_s * 1e3:.1f}ms (median of {STEPS}, {BATCH * SIZE * SIZE / step_s:.0f} "
        f"px/s); worst kernel err {kernel_worst:.3e}; loss err {max(err0, err1):.3e}")
    check(strategy == "reversible", f"coupled backward strategy is {strategy}")


def four_chips():
    import jax
    import numpy as np

    from repro.config import TrainConfig
    from repro.configs.flows import GLOW_SCANNED
    from repro.core import build_glow_scanned
    from repro.data import SyntheticImages
    from repro.dist.flow import shard_batch
    from repro.launch.mesh import make_auto_mesh
    from repro.serve import FlowServeEngine
    from repro.train import train_flow

    mesh = make_auto_mesh((4, 1))
    widths = dict(n_scales=GLOW_SCANNED.n_scales, k_steps=GLOW_SCANNED.k_steps,
                  hidden=GLOW_SCANNED.hidden, grad_mode="coupled")
    single = build_glow_scanned(**widths)
    dp = build_glow_scanned(**widths, psum_axis="data")
    data = SyntheticImages(size=SIZE, batch=BATCH, seed=0)
    x0 = data.batch_at(0)

    log(f"data-parallel train step on mesh {dict(mesh.shape)} vs one device")
    results = {}
    for name, flow, m in (("single", single, None), ("dp", dp, mesh)):
        with tempfile.TemporaryDirectory() as ckpt_dir:
            # one step from zero moments: mu = (1 - b1) * grad (no clipping)
            cfg = TrainConfig(steps=1, lr=1e-3, warmup_steps=1, grad_clip=0.0,
                              checkpoint_dir=ckpt_dir, max_restarts=0)
            res = train_flow(flow, data, cfg, x0, mesh=m)
        check(res.restarts == 0, f"{name}: training restarted")
        results[name] = res
    one, par = results["single"], results["dp"]
    loss_err = abs(par.losses[0] - one.losses[0]) / (1 + abs(one.losses[0]))
    log(f"  loss single {one.losses[0]:.7f} dp {par.losses[0]:.7f}: "
        f"err {loss_err:.3e} (tol {DP_TOL:.0e})")
    check(loss_err <= DP_TOL, f"DP loss differs by {loss_err:.3e}")
    scale = 1.0 - cfg.b1
    g_err = 0.0
    for a, b in zip(jax.tree_util.tree_leaves(par.opt_state["mu"]),
                    jax.tree_util.tree_leaves(one.opt_state["mu"])):
        a, b = np.asarray(a) / scale, np.asarray(b) / scale
        g_err = max(g_err, float(np.max(np.abs(a - b) / (1 + np.abs(b)))))
    log(f"  gradients: max err {g_err:.3e} (tol {DP_TOL:.0e})")
    check(g_err <= DP_TOL, f"DP gradients differ by {g_err:.3e}")
    devs = set(mesh.devices.flat)
    for leaf in jax.tree_util.tree_leaves(par.params):
        check(leaf.sharding.device_set == devs, "a DP parameter is not on every chip")
    shards = shard_batch(x0, mesh).addressable_shards
    check({s.device for s in shards} == devs and all(
        s.data.shape[0] == BATCH // 4 for s in shards), "batch not split over 4 chips")
    log("  placement: params replicated on 4 chips, batch split 4 ways")

    log("batch-sharded sampling via FlowServeEngine vs one device")
    params = one.params
    z_like = jax.eval_shape(lambda p, x: single.forward(p, x)[0], params, x0)
    key = jax.random.PRNGKey(2)
    sharded = FlowServeEngine(single, params, mesh=mesh).sample(key, z_like)
    ref = FlowServeEngine(single, params).sample(key, z_like)
    s_err = 0.0
    for a, b in zip(jax.tree_util.tree_leaves(sharded), jax.tree_util.tree_leaves(ref)):
        check({s.device for s in a.addressable_shards} == devs,
              "samples not spread over 4 chips")
        a, b = np.asarray(a), np.asarray(b)
        s_err = max(s_err, float(np.max(np.abs(a - b) / (1 + np.abs(b)))))
    log(f"  samples: max err {s_err:.3e} (tol {DP_TOL:.0e})")
    check(s_err <= DP_TOL, f"sharded samples differ by {s_err:.3e}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the data-parallel step and sharded "
                         "sampling checks on four chips")
    args = ap.parse_args()
    n_chips = 4 if args.four_chips else 1
    devices = require_chip(n_chips)
    if args.four_chips:
        four_chips()
    else:
        one_chip()
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
