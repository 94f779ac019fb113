"""Flow-step megakernel + kernel-config-layer tests.

* megakernel parity vs the composed ActNorm -> Conv1x1 -> AffineCoupling
  layers (fwd y/logdet, bwd gx/gparams <= 1e-4) across float32/bfloat16 and
  ragged spatial extents — on the reference path AND with the Pallas kernel
  bodies forced (interpret);
* the backend-aware interpret/reference resolution and its env override;
* the measured block_m autotuner and its persistent cache;
* scanned-GLOW engagement: one fused dispatch per flow step in the coupled
  backward, and the backend-resolved coupled-backward strategy.
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import GlowStepStack, InvertibleChain, value_and_grad_nll
from repro.core.glow_scan import (
    build_glow_scanned,
    default_scan_unroll,
    resolve_coupled_bwd,
)
from repro.kernels import common as kcommon
from repro.kernels.flowstep import ops as fops
from repro.kernels.flowstep.flowstep import (
    BLOCK_ELEMS,
    coupling_half_bwd,
    flowstep_fwd,
    flowstep_inv,
    lane_tiling,
    spine_bwd,
)
from repro.kernels.flowstep.ref import (
    channel_mix,
    coupling_half_bwd_ref,
    flowstep_fwd_ref,
    flowstep_inv_ref,
    spine_bwd_ref,
)

RNG = jax.random.PRNGKey(20260728)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=1e-4, atol=1e-4)


def _step_inputs(b, c, m, dtype=jnp.float32):
    """Channel-major (B, C, M) operands of one flow step; ``h`` holds raw
    on its first ``c // 2`` channels and t on the next ``c // 2``."""
    ks = jax.random.split(RNG, 5)
    ca = c // 2
    x = jax.random.normal(ks[0], (b, c, m), dtype)
    an_ls = 0.1 * jax.random.normal(ks[1], (c,))
    an_b = 0.1 * jax.random.normal(ks[2], (c,))
    w = jax.random.normal(ks[3], (c, c)) / jnp.sqrt(c) + jnp.eye(c)
    h = jax.random.normal(ks[4], (b, 2 * ca, m), dtype)
    return x, an_ls, an_b, w, h


def _close(a, r, tol, name):
    np.testing.assert_allclose(
        np.asarray(a, np.float64), np.asarray(r, np.float64), **tol, err_msg=name
    )


def _f64(fn, *args):
    """``fn`` of float64 copies of ``args``: the oracle in float64, which
    holds the kernels' long f32 sums (gW, g_log_s, g_b) to 1e-4 where the
    float32 oracle's own rounding would not."""
    with jax.enable_x64(True):
        out = fn(*(jnp.asarray(np.asarray(a, np.float64)) for a in args))
        return jax.tree_util.tree_map(lambda v: np.asarray(v, np.float64), out)


# ---------------------------------------------------------------------------
# kernel-body parity vs the jnp oracle (forced interpret)
# ---------------------------------------------------------------------------


@pytest.fixture
def force_interpret(monkeypatch):
    monkeypatch.setenv(kcommon.INTERPRET_ENV, "1")
    yield


# (M, C, block_m): GLOW_FIG1's three scales at 256x256, each with several
# lane blocks per batch element; ragged M (one whole-M block); an odd C;
# ragged M padded to several blocks
KERNEL_SHAPES = [
    (16384, 12, 2048), (4096, 24, 1024), (1024, 48, 256),
    (300, 12, None), (28, 6, None), (640, 5, 128),
    (300, 12, 128),  # ragged M over one block: zero-padded lanes
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,c,bm", KERNEL_SHAPES)
def test_flowstep_fwd_kernel_parity(force_interpret, m, c, bm, dtype):
    x, an_ls, an_b, w, h = _step_inputs(2, c, m, dtype)
    y, ld = flowstep_fwd(x, an_ls, an_b, w, h, block_m=bm)
    y_r, ld_r = flowstep_fwd_ref(x, an_ls, an_b, w, h)
    _close(y, y_r, _tol(dtype), "y")
    np.testing.assert_allclose(np.asarray(ld), np.asarray(ld_r), rtol=1e-3, atol=1e-3)
    # inverse kernel round-trips through the pair
    w_inv = jnp.linalg.inv(w)
    x2 = flowstep_inv(y, an_ls, an_b, w_inv, h, block_m=bm)
    x2_r = flowstep_inv_ref(y_r, an_ls, an_b, w_inv, h)
    _close(x2, x2_r, _tol(dtype), "x")
    if dtype == jnp.float32:
        _close(x2, x, _tol(dtype), "round trip")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,c,bm", KERNEL_SHAPES)
def test_spine_bwd_kernel_parity(force_interpret, m, c, bm, dtype):
    ks = jax.random.split(RNG, 3)
    _x, an_ls, an_b, w, _h = _step_inputs(2, c, m)
    x2 = jax.random.normal(ks[0], (2, c, m), dtype)
    gx2 = jax.random.normal(ks[1], (2, c, m), dtype)
    gxb = jax.random.normal(ks[2], (2, c - c // 2, m), dtype)
    w_inv = jnp.linalg.inv(w)
    out_k = spine_bwd(x2, gx2, gxb, w, w_inv, an_ls, an_b, block_m=bm)
    out_r = spine_bwd_ref(x2, gx2, gxb, w, w_inv, an_ls, an_b)
    if dtype == jnp.float32:  # the sums against the float64 oracle
        out_r = out_r[:2] + _f64(spine_bwd_ref, x2, gx2, gxb, w, w_inv, an_ls, an_b)[2:]
    for i, (a, r, name) in enumerate(zip(out_k, out_r, ("x", "gx", "gw", "g_log_s", "g_b"))):
        tol = _tol(dtype) if i < 2 or dtype == jnp.float32 else dict(rtol=5e-2, atol=5e-2)
        _close(a, r, tol, f"{name} (m={m}, c={c}, {dtype.__name__})")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,c,bm", KERNEL_SHAPES)
def test_coupling_half_bwd_kernel_parity(force_interpret, m, c, bm, dtype):
    ks = jax.random.split(RNG, 2)
    y, _ls, _b, _w, h = _step_inputs(2, c, m, dtype)
    gy = jax.random.normal(ks[0], y.shape, dtype)
    gld = jax.random.normal(ks[1], (2,))
    out_k = coupling_half_bwd(y, h, gy, gld, block_m=bm)
    out_r = coupling_half_bwd_ref(y, h, gy, gld)
    for a, r, name in zip(out_k, out_r, ("x2", "gh", "gx2")):
        assert a.shape == r.shape and a.dtype == r.dtype, name
        _close(a, r, _tol(dtype), f"{name} (m={m}, c={c}, {dtype.__name__})")


def test_fused_flowstep_custom_vjp_matches_autodiff(force_interpret):
    """Gradients through the megakernel's custom VJP (coupling_half_bwd +
    spine_bwd kernels) == plain AD through the oracle, <= 1e-4."""
    x, an_ls, an_b, w, h = _step_inputs(2, 6, 256)
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    gy = jax.random.normal(ks[0], x.shape)
    gld = jax.random.normal(ks[1], (x.shape[0],))

    def loss(fwd):
        def L(x_, ls_, b_, w_, h_):
            y, ld = fwd(x_, ls_, b_, w_, h_)
            return jnp.sum(y * gy) + jnp.sum(ld * gld)

        return jax.grad(L, argnums=(0, 1, 2, 3, 4))

    g_k = loss(functools.partial(fops.fused_flowstep_fwd, block_m=128))(
        x, an_ls, an_b, w, h)
    g_r = loss(flowstep_fwd_ref)(x, an_ls, an_b, w, h)
    for a, r, name in zip(g_k, g_r, ("gx", "g_an_ls", "g_an_b", "gw", "gh")):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(r), rtol=1e-4, atol=1e-4, err_msg=name
        )


@pytest.mark.parametrize("m,c,bm", [(1024, 12, 256), (4096, 24, 1024), (300, 6, None)])
def test_kernel_backward_matches_autodiff_of_reference_step(force_interpret, m, c, bm):
    """The reversible backward as the scanned GLOW runs it — coupling half,
    the conditioner's VJP, then the spine with the conditioner's input
    cotangent — against plain AD through the oracle step, with gW and the
    actnorm gradients accumulated over the batch and several lane blocks."""
    b, ca = 3, c // 2
    x, an_ls, an_b, w, _h = _step_inputs(b, c, m)
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    net_w = 0.3 * jax.random.normal(ks[0], (c - ca, 2 * ca))
    gy = jax.random.normal(ks[1], x.shape)
    gld = jax.random.normal(ks[2], (b,))

    def net(w_, xb):  # a channel-major stand-in for the conditioner
        return jnp.sin(channel_mix(w_, xb))

    def step(x_, ls_, b_, w_, net_w_):
        xb = channel_mix(w_, x_ * jnp.exp(ls_)[:, None] + b_[:, None])[:, ca:]
        return flowstep_fwd_ref(x_, ls_, b_, w_, net(net_w_, xb))

    def grads(x_, ls_, b_, w_, net_w_, gy_, gld_):
        return jax.vjp(step, x_, ls_, b_, w_, net_w_)[1]((gy_, gld_))

    y, _ld = step(x, an_ls, an_b, w, net_w)
    g_r = _f64(grads, x, an_ls, an_b, w, net_w, gy, gld)

    x2, gh, gx2 = coupling_half_bwd(y, net(net_w, channel_mix(
        w, x * jnp.exp(an_ls)[:, None] + an_b[:, None])[:, ca:]), gy, gld, block_m=bm)
    g_net, gxb = jax.vjp(net, net_w, x2[:, ca:])[1](gh)
    x_k, gx, gw, g_ls, g_b = spine_bwd(
        x2, gx2, gxb, w, jnp.linalg.inv(w), an_ls, an_b, block_m=bm)
    _close(x_k, x, dict(rtol=1e-4, atol=1e-4), "x rebuilt")
    for a, r, name in zip((gx, g_ls, g_b, gw, g_net), g_r,
                          ("gx", "g_an_ls", "g_an_b", "gw", "g_net")):
        _close(a, r, dict(rtol=1e-4, atol=1e-4), name)


# ---------------------------------------------------------------------------
# megakernel step vs the composed unrolled layers
# ---------------------------------------------------------------------------


def _stack_and_composed(rng, x, k_steps=2, hidden=8):
    """A GlowStepStack and the equivalent unrolled ActNorm/Conv1x1/
    AffineCoupling chain sharing the *same* parameters."""
    from repro.core import ActNorm, AffineCoupling, Conv1x1
    from repro.nn.nets import CouplingCNN

    stack = GlowStepStack(k_steps, hidden=hidden, grad_mode="autodiff")
    sp = stack.init(rng, x)
    factory = lambda c_out: CouplingCNN(c_out, hidden=hidden)
    layers, params = [], []
    for i in range(k_steps):
        p_i = jax.tree_util.tree_map(lambda v: v[i], sp)
        layers += [ActNorm(), Conv1x1(), AffineCoupling(factory)]
        params += [p_i["an"], p_i["lu"], {"net": p_i["net"]}]
    return stack, sp, InvertibleChain(layers, grad_mode="autodiff"), tuple(params)


@pytest.mark.parametrize("shape", [(2, 8, 8, 4), (3, 5, 6, 4)])  # ragged extents
def test_megakernel_step_matches_composed_layers_fwd(shape):
    x = jax.random.normal(RNG, shape)
    stack, sp, chain, cp = _stack_and_composed(jax.random.PRNGKey(1), x)
    y_s, ld_s = stack.forward(sp, x)
    y_c, ld_c = chain.forward(cp, x)
    np.testing.assert_allclose(np.asarray(y_s), np.asarray(y_c), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(ld_s), np.asarray(ld_c), rtol=1e-5, atol=1e-5)
    x2 = stack.inverse(sp, y_s)
    np.testing.assert_allclose(np.asarray(x2), np.asarray(x), rtol=1e-4, atol=1e-4)


def test_megakernel_step_matches_composed_layers_fwd_bf16():
    x = jax.random.normal(RNG, (2, 4, 4, 4), jnp.bfloat16)
    stack, sp, chain, cp = _stack_and_composed(jax.random.PRNGKey(1), x)
    y_s, ld_s = stack.forward(sp, x)
    y_c, ld_c = chain.forward(cp, x)
    np.testing.assert_allclose(
        np.asarray(y_s, np.float32), np.asarray(y_c, np.float32), rtol=2e-2, atol=2e-2
    )
    np.testing.assert_allclose(
        np.asarray(ld_s, np.float32), np.asarray(ld_c, np.float32), rtol=2e-2, atol=2e-2
    )


@pytest.mark.parametrize("interpret", [False, True])
@pytest.mark.parametrize("shape", [(2, 8, 8, 4), (3, 5, 6, 4)])
def test_megakernel_bwd_matches_composed_layers(shape, interpret, monkeypatch):
    """Coupled (megakernel) backward gradients vs plain AD through the
    composed layers, <= 1e-4 — reference path and Pallas kernel bodies."""
    if interpret:
        monkeypatch.setenv(kcommon.INTERPRET_ENV, "1")
    x = jax.random.normal(RNG, shape)
    stack, sp, chain, cp = _stack_and_composed(jax.random.PRNGKey(1), x)
    l_c, g_c = value_and_grad_nll(chain.forward, cp, x)
    coupled = InvertibleChain(
        [GlowStepStack(2, hidden=8, grad_mode="coupled", coupled_bwd="reversible")],
        grad_mode="coupled",
    )
    l_s, g_s = value_and_grad_nll(coupled.forward, (sp,), x)
    assert abs(float(l_s - l_c)) < 1e-5
    flat_c = jnp.concatenate([v.ravel() for v in jax.tree_util.tree_leaves(g_c)
                              if jnp.issubdtype(jnp.asarray(v).dtype, jnp.inexact)])
    flat_s = jnp.concatenate([v.ravel() for v in jax.tree_util.tree_leaves(g_s)
                              if jnp.issubdtype(jnp.asarray(v).dtype, jnp.inexact)])
    assert flat_c.size == flat_s.size
    # same trees modulo stacking: compare sorted magnitudes AND a direct
    # per-leaf walk through the stacked structure
    p0 = jax.tree_util.tree_leaves(g_s)
    assert all(bool(jnp.all(jnp.isfinite(v))) for v in p0
               if jnp.issubdtype(jnp.asarray(v).dtype, jnp.inexact))
    gs_stack = g_s[0]
    for i in range(2):
        gi = jax.tree_util.tree_map(
            lambda v: v[i] if jnp.issubdtype(jnp.asarray(v).dtype, jnp.inexact) else v,
            gs_stack,
        )
        for part, ref in (("an", g_c[3 * i]), ("lu", g_c[3 * i + 1]),
                          ("net", g_c[3 * i + 2]["net"])):
            d = jax.tree_util.tree_map(
                lambda a, b: float(jnp.max(jnp.abs(jnp.asarray(a, jnp.float32)
                                                   - jnp.asarray(b, jnp.float32))))
                if jnp.issubdtype(jnp.asarray(a).dtype, jnp.inexact) else 0.0,
                gi[part], ref,
            )
            m = max(jax.tree_util.tree_leaves(d) or [0.0])
            assert m < 1e-4, f"step {i} {part}: max grad diff {m}"


# ---------------------------------------------------------------------------
# kernel config layer: interpret resolution + autotuner
# ---------------------------------------------------------------------------


def test_kernel_path_resolution(monkeypatch):
    monkeypatch.delenv(kcommon.INTERPRET_ENV, raising=False)
    assert kcommon.kernel_path() == (
        "compiled" if jax.default_backend() in kcommon.COMPILED_BACKENDS
        else "reference"
    )
    monkeypatch.setenv(kcommon.INTERPRET_ENV, "1")
    assert kcommon.kernel_path() == "interpret"
    assert kcommon.resolve_interpret(None) is True
    monkeypatch.setenv(kcommon.INTERPRET_ENV, "0")
    assert kcommon.kernel_path() == "compiled"
    assert kcommon.resolve_interpret(None) is False
    # explicit beats everything
    assert kcommon.resolve_interpret(True) is True


def test_resolution_logged_once(monkeypatch, caplog):
    monkeypatch.delenv(kcommon.INTERPRET_ENV, raising=False)
    kcommon.reset_kernel_config()
    import logging

    with caplog.at_level(logging.INFO, logger="repro.kernels"):
        kcommon.kernel_path()
        kcommon.kernel_path()
        kcommon.kernel_path()
    assert len([r for r in caplog.records if "kernel path" in r.message]) == 1


def test_candidate_block_ms():
    cands = kcommon.candidate_block_ms(1024)
    assert cands == [64, 128, 256, 512, 1024]
    assert all(1024 % b == 0 for b in cands)
    # 8-aligned divisors only, or the whole axis
    assert kcommon.candidate_block_ms(600) == [40, 120, 200, 600]
    assert kcommon.candidate_block_ms(300) == [300]


@pytest.mark.parametrize("m,target,align,want", [
    (16384, 5000, 128, 4096),   # the largest multiple of 128 dividing m
    (1024, 4096, 128, 1024),    # m within the target: one block
    (64, 4096, 128, 64),        # m under a lane: one block
    (1200, 512, 128, 1200),     # no multiple of 128 divides m: one block
    (640, 512, 128, 128),
    (600, 256, 8, 200),         # the sublane rule, unchanged
])
def test_pick_block_m_lane_rule(m, target, align, want):
    assert kcommon.pick_block_m(m, target, align=align) == want


def test_lane_tiling_default_and_padding():
    # GLOW_FIG1 at 256x256: half of M at scale 1, the whole M after
    assert [lane_tiling(m, c) for m, c in ((16384, 12), (4096, 24), (1024, 48))] \
        == [(8192, 16384), (4096, 4096), (1024, 1024)]
    assert lane_tiling(16384, 12, 1000) == (512, 16384)  # an explicit block, made legal
    assert lane_tiling(300, 12) == (300, 300)            # ragged, one block
    # ragged and over one block's size (a 1000x1000 image at scale 1): padded
    # to a multiple of 128, in blocks within the cap
    bm, mp = lane_tiling(250000, 12)
    assert mp == 250112 and mp % bm == 0 and bm % 128 == 0 and bm * 16 <= BLOCK_ELEMS
    assert lane_tiling(300, 12, 128) == (128, 384)


def test_tuned_block_m_measures_once_and_persists(tmp_path, monkeypatch):
    """The autotuner measures each candidate once, persists the winner, and
    later processes (fresh in-memory cache) skip measurement entirely."""
    monkeypatch.setenv(kcommon.AUTOTUNE_CACHE_ENV, str(tmp_path / "tune.json"))
    monkeypatch.setenv(kcommon.INTERPRET_ENV, "0")  # force the compiled path
    kcommon.reset_kernel_config()
    calls = []

    def measure(bm):
        calls.append(bm)
        return abs(bm - 128) + 1.0  # 128 wins

    best = kcommon.tuned_block_m("op", (2, 1024, 8), jnp.float32, measure)
    assert best == 128
    assert sorted(calls) == kcommon.candidate_block_ms(1024)
    # cached: no further measurement, same answer
    calls.clear()
    assert kcommon.tuned_block_m("op", (2, 1024, 8), jnp.float32, measure) == 128
    assert calls == []
    # fresh process (in-memory cache dropped): reads the persisted file
    kcommon.reset_kernel_config()
    assert kcommon.tuned_block_m("op", (2, 1024, 8), jnp.float32, measure) == 128
    assert calls == []
    # under tracing the ops layer passes measure=None: the persisted winner
    # must still be served (cache lookup, no measurement)
    assert kcommon.tuned_block_m("op", (2, 1024, 8), jnp.float32, None) == 128
    # unknown shape without a measure: deterministic divisor pick
    assert kcommon.tuned_block_m("op", (2, 512, 8), jnp.float32, None) == 256
    kcommon.reset_kernel_config()


def test_tuned_block_m_off_compiled_path(monkeypatch):
    """On interpret/reference paths timing is emulation noise — the tuner
    must fall back to the deterministic divisor pick, measuring nothing."""
    monkeypatch.setenv(kcommon.INTERPRET_ENV, "1")

    def measure(bm):  # pragma: no cover - must not run
        raise AssertionError("measured on a non-compiled path")

    assert kcommon.tuned_block_m("op", (2, 600, 8), jnp.float32, measure) == 200


def test_resolve_block_m_explicit_legalized():
    x = jnp.zeros((2, 600, 4))
    assert kcommon.resolve_block_m("op", x, 256) == 200  # divisor <= request
    assert kcommon.resolve_block_m("op", x, None) == 200


# ---------------------------------------------------------------------------
# scanned GLOW: engagement, strategy resolution, unroll policy
# ---------------------------------------------------------------------------


def test_one_fused_dispatch_per_flow_step(monkeypatch):
    """The coupled backward of a GlowStepStack dispatches the fused coupling
    backward and the fused spine backward exactly once per scan body trace —
    i.e. one fused dispatch per flow step, no per-sub-layer launches."""
    counts = {"coupling": 0, "spine": 0, "fwd": 0}
    orig_c, orig_s, orig_f = (
        fops.fused_coupling_half_bwd, fops.fused_spine_bwd, fops.fused_flowstep_fwd
    )
    monkeypatch.setattr(fops, "fused_coupling_half_bwd",
                        lambda *a, **k: (counts.__setitem__("coupling", counts["coupling"] + 1), orig_c(*a, **k))[1])
    monkeypatch.setattr(fops, "fused_spine_bwd",
                        lambda *a, **k: (counts.__setitem__("spine", counts["spine"] + 1), orig_s(*a, **k))[1])
    monkeypatch.setattr(fops, "fused_flowstep_fwd",
                        lambda *a, **k: (counts.__setitem__("fwd", counts["fwd"] + 1), orig_f(*a, **k))[1])
    x = jax.random.normal(RNG, (2, 4, 4, 4))
    stack = GlowStepStack(3, hidden=8, grad_mode="coupled", coupled_bwd="reversible")
    chain = InvertibleChain([stack], grad_mode="coupled")
    params = chain.init(RNG, x)
    value_and_grad_nll(chain.forward, params, x)
    # scan traces the step body once regardless of depth: one fused coupling
    # + one fused spine dispatch per flow step, zero stray launches
    assert counts["coupling"] == 1 and counts["spine"] == 1
    # the forward megakernel engages only on the kernel path (off-CPU);
    # the reference path inlines the fused jnp step instead
    expected_fwd = 0 if kcommon.kernel_path() == "reference" else 1
    assert counts["fwd"] == expected_fwd


def test_coupled_bwd_strategy_resolution(monkeypatch):
    monkeypatch.delenv("REPRO_COUPLED_BWD", raising=False)
    auto = resolve_coupled_bwd("auto")
    assert auto == (
        "reversible" if jax.default_backend() in kcommon.COMPILED_BACKENDS
        else "stored"
    )
    assert resolve_coupled_bwd("reversible") == "reversible"
    monkeypatch.setenv("REPRO_COUPLED_BWD", "reversible")
    assert resolve_coupled_bwd("auto") == "reversible"
    monkeypatch.delenv("REPRO_COUPLED_BWD")
    with pytest.raises(ValueError):
        resolve_coupled_bwd("bogus")


def test_coupled_strategies_agree(monkeypatch):
    """Both coupled backward strategies produce the same gradients (and both
    match plain autodiff through the same scanned forward)."""
    monkeypatch.delenv("REPRO_COUPLED_BWD", raising=False)
    x = jax.random.normal(RNG, (2, 8, 8, 3))
    ref = build_glow_scanned(n_scales=2, k_steps=2, hidden=8, grad_mode="autodiff")
    params = ref.init(RNG, x)
    l_ref, g_ref = value_and_grad_nll(ref.forward, params, x)
    for strategy in ("reversible", "stored"):
        flow = build_glow_scanned(
            n_scales=2, k_steps=2, hidden=8, grad_mode="coupled",
            coupled_bwd=strategy,
        )
        l, g = value_and_grad_nll(flow.forward, params, x)
        assert abs(float(l - l_ref)) < 1e-6, strategy
        d = jax.tree_util.tree_map(
            lambda a, b: float(jnp.max(jnp.abs(jnp.asarray(a) - jnp.asarray(b))))
            if jnp.issubdtype(jnp.asarray(a).dtype, jnp.inexact) else 0.0,
            g, g_ref,
        )
        m = max(jax.tree_util.tree_leaves(d) or [0.0])
        assert m < 1e-4, f"{strategy}: max grad diff {m}"


def test_default_scan_unroll(monkeypatch):
    monkeypatch.delenv("REPRO_SCAN_UNROLL", raising=False)
    expected = 1 if jax.default_backend() in kcommon.COMPILED_BACKENDS else 8
    assert default_scan_unroll(8) == expected
    monkeypatch.setenv("REPRO_SCAN_UNROLL", "2")
    assert default_scan_unroll(8) == 2
    monkeypatch.setenv("REPRO_SCAN_UNROLL", "99")
    assert default_scan_unroll(8) == 8  # clamped to k_steps


def test_scanned_glow_conditioner_eval_count():
    """The coupled (reversible) backward evaluates each step's conditioner
    exactly twice per training step (1 forward + 1 backward trace) — the
    megakernel boundary keeps the conditioner an XLA island, evaluated once
    per side of the step."""
    from conformance import CountingNet
    from repro.nn.nets import CouplingCNN

    counter = [0]
    factory = lambda c_out: CountingNet(CouplingCNN(c_out, hidden=8), counter)
    stack = GlowStepStack(3, hidden=8, grad_mode="coupled",
                          coupled_bwd="reversible", conditioner_factory=factory)
    chain = InvertibleChain([stack], grad_mode="coupled")
    x = jax.random.normal(RNG, (2, 4, 4, 4))
    params = chain.init(RNG, x)
    counter[0] = 0
    value_and_grad_nll(chain.forward, params, x)
    # scan body traced once: 1 fwd + 1 bwd conditioner trace
    assert counter[0] == 2, counter[0]


def test_reference_kernels_scope(monkeypatch):
    """``reference_kernels()`` routes the hot path through the oracles on
    any backend, for the block only — the chip's parity check."""
    monkeypatch.setenv(kcommon.INTERPRET_ENV, "1")
    assert kcommon.kernel_path() == "interpret"
    with kcommon.reference_kernels():
        assert kcommon.kernel_path() == "reference"
        x, an_ls, an_b, w, h = _step_inputs(2, 6, 16)
        y, ld = fops.fused_flowstep_fwd(x, an_ls, an_b, w, h)
        y_r, ld_r = flowstep_fwd_ref(x, an_ls, an_b, w, h)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(y_r))
    assert kcommon.kernel_path() == "interpret"


def test_tune_key_names_the_device_kind():
    """A block_m measured on one chip generation is never served to another."""
    key = kcommon._tune_key("op", (2, 1024, 8), jnp.float32)
    assert jax.devices()[0].device_kind in key.split("|")
