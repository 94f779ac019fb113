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
from repro.kernels.flowstep.flowstep import flowstep_fwd, flowstep_inv, spine_bwd
from repro.kernels.flowstep.ref import (
    flowstep_fwd_ref,
    flowstep_inv_ref,
    spine_bwd_ref,
)

RNG = jax.random.PRNGKey(20260728)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=1e-4, atol=1e-4)


def _step_inputs(b, m, c, dtype=jnp.float32):
    ks = jax.random.split(RNG, 6)
    ca = c // 2
    x = jax.random.normal(ks[0], (b, m, c), dtype)
    an_ls = 0.1 * jax.random.normal(ks[1], (c,))
    an_b = 0.1 * jax.random.normal(ks[2], (c,))
    w = jax.random.normal(ks[3], (c, c)) / jnp.sqrt(c) + jnp.eye(c)
    raw = jax.random.normal(ks[4], (b, m, ca), dtype)
    t = jax.random.normal(ks[5], (b, m, ca), dtype)
    return x, an_ls, an_b, w, raw, t


# ---------------------------------------------------------------------------
# kernel-body parity vs the jnp oracle (forced interpret)
# ---------------------------------------------------------------------------


@pytest.fixture
def force_interpret(monkeypatch):
    monkeypatch.setenv(kcommon.INTERPRET_ENV, "1")
    yield


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m", [256, 300, 28])
def test_flowstep_fwd_kernel_parity(force_interpret, m, dtype):
    x, an_ls, an_b, w, raw, t = _step_inputs(2, m, 6, dtype)
    bm = kcommon.pick_block_m(m)
    y, ld = flowstep_fwd(x, an_ls, an_b, w, raw, t, block_m=bm)
    y_r, ld_r = flowstep_fwd_ref(x, an_ls, an_b, w, raw, t)
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(y_r, np.float32), **_tol(dtype)
    )
    np.testing.assert_allclose(np.asarray(ld), np.asarray(ld_r), rtol=1e-3, atol=1e-3)
    # inverse kernel round-trips through the pair
    w_inv = jnp.linalg.inv(w)
    x2 = flowstep_inv(y, an_ls, an_b, w_inv, raw, t, block_m=bm)
    x2_r = flowstep_inv_ref(y_r, an_ls, an_b, w_inv, raw, t)
    np.testing.assert_allclose(
        np.asarray(x2, np.float32), np.asarray(x2_r, np.float32), **_tol(dtype)
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m", [256, 300, 28])
def test_spine_bwd_kernel_parity(force_interpret, m, dtype):
    ks = jax.random.split(RNG, 2)
    _x, an_ls, an_b, w, _raw, _t = _step_inputs(2, m, 6)
    x2 = jax.random.normal(ks[0], (2, m, 6), dtype)
    gx2 = jax.random.normal(ks[1], (2, m, 6), dtype)
    w_inv = jnp.linalg.inv(w)
    bm = kcommon.pick_block_m(m)
    out_k = spine_bwd(x2, gx2, w, w_inv, an_ls, an_b, block_m=bm)
    out_r = spine_bwd_ref(x2, gx2, w, w_inv, an_ls, an_b)
    gw_tol = dict(rtol=5e-2, atol=5e-2) if dtype == jnp.bfloat16 else dict(rtol=1e-4, atol=1e-4)
    for a, r, name in zip(out_k, out_r, ("x", "gx", "gw", "g_log_s", "g_b")):
        tol = gw_tol if name in ("gw", "g_log_s", "g_b") else _tol(dtype)
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(r, np.float32), **tol,
            err_msg=f"{name} (m={m}, {dtype.__name__})",
        )


def test_fused_flowstep_custom_vjp_matches_autodiff(force_interpret):
    """Gradients through the megakernel's custom VJP (coupling_bwd +
    spine_bwd kernels) == plain AD through the oracle, <= 1e-4."""
    x, an_ls, an_b, w, raw, t = _step_inputs(2, 64, 6)
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    gy = jax.random.normal(ks[0], x.shape)
    gld = jax.random.normal(ks[1], (x.shape[0],))

    def loss(fwd):
        def L(x_, ls_, b_, w_, raw_, t_):
            y, ld = fwd(x_, ls_, b_, w_, raw_, t_)
            return jnp.sum(y * gy) + jnp.sum(ld * gld)

        return jax.grad(L, argnums=(0, 1, 2, 3, 4, 5))

    g_k = loss(fops.fused_flowstep_fwd)(x, an_ls, an_b, w, raw, t)
    g_r = loss(flowstep_fwd_ref)(x, an_ls, an_b, w, raw, t)
    for a, r, name in zip(g_k, g_r, ("gx", "g_an_ls", "g_an_b", "gw", "graw", "gt")):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(r), rtol=1e-4, atol=1e-4, err_msg=name
        )


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
        x, an_ls, an_b, w, raw, t = _step_inputs(2, 16, 6)
        y, ld = fops.fused_flowstep_fwd(x, an_ls, an_b, w, raw, t)
        y_r, ld_r = flowstep_fwd_ref(x, an_ls, an_b, w, raw, t)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(y_r))
    assert kcommon.kernel_path() == "interpret"


def test_tune_key_names_the_device_kind():
    """A block_m measured on one chip generation is never served to another."""
    key = kcommon._tune_key("op", (2, 1024, 8), jnp.float32)
    assert jax.devices()[0].device_kind in key.split("|")
