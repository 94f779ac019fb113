"""Per-kernel correctness: sweep shapes/dtypes, assert_allclose against the
pure-jnp oracles (interpret=True executes the kernel body on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.attention.ops import flash_sdpa
from repro.kernels.attention.ref import attention_ref
from repro.kernels.common import pick_block_m
from repro.kernels.conv1x1.ops import invertible_conv1x1
from repro.kernels.conv1x1.ref import conv1x1_mm_ref
from repro.kernels.coupling.ops import (
    fused_coupling_bwd,
    fused_coupling_fwd,
    fused_coupling_inv,
)
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


@pytest.fixture(autouse=True)
def _exercise_kernel_bodies(monkeypatch):
    """These tests pin the *Pallas kernel bodies* against the jnp oracles, so
    the public wrappers must not take the reference dispatch (the CPU
    default) — force interpret so every call executes the kernel."""
    from repro.kernels.common import INTERPRET_ENV

    monkeypatch.setenv(INTERPRET_ENV, "1")
    yield


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# coupling
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(2, 256, 8), (1, 512, 3), (3, 1024, 16)])
def test_coupling_kernel(shape, dtype):
    ks = jax.random.split(RNG, 3)
    x = jax.random.normal(ks[0], shape, dtype)
    raw = jax.random.normal(ks[1], shape, dtype)
    t = jax.random.normal(ks[2], shape, dtype)
    y, ld = fused_coupling_fwd(x, raw, t)
    y_ref, ld_ref = coupling_fwd_ref(x, raw, t)
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(y_ref, np.float32), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(ld), np.asarray(ld_ref), rtol=1e-3, atol=1e-3)
    # inverse round-trips through the kernel pair
    x2 = fused_coupling_inv(y, raw, t)
    x2_ref = coupling_inv_ref(y_ref, raw, t)
    np.testing.assert_allclose(np.asarray(x2, np.float32), np.asarray(x2_ref, np.float32), **_tol(dtype))
    np.testing.assert_allclose(
        np.asarray(x2, np.float32), np.asarray(x, np.float32), rtol=5e-2 if dtype == jnp.bfloat16 else 1e-4,
        atol=5e-2 if dtype == jnp.bfloat16 else 1e-4,
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(2, 256, 8), (1, 512, 3)])
def test_coupling_backward_kernel(shape, dtype):
    """The fused backward kernel matches its oracle: reconstruction + all
    cotangents (incl. the logdet term) in one pass."""
    ks = jax.random.split(RNG, 5)
    y = jax.random.normal(ks[0], shape, dtype)
    raw = jax.random.normal(ks[1], shape, dtype)
    t = jax.random.normal(ks[2], shape, dtype)
    gy = jax.random.normal(ks[3], shape, dtype)
    gld = jax.random.normal(ks[4], (shape[0],))
    out_k = fused_coupling_bwd(y, raw, t, gy, gld)
    out_ref = coupling_bwd_ref(y, raw, t, gy, gld)
    for a, b, name in zip(out_k, out_ref, ("x", "gx", "graw", "gt")):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            **_tol(dtype), err_msg=name,
        )


def test_coupling_custom_vjp_matches_autodiff():
    """Gradients through the Pallas kernel's custom VJP == plain AD through
    the jnp oracle (acceptance: <= 1e-4)."""
    ks = jax.random.split(RNG, 5)
    shape = (2, 256, 8)
    x, raw, t = (jax.random.normal(ks[i], shape) for i in range(3))
    gy = jax.random.normal(ks[3], shape)
    gld = jax.random.normal(ks[4], (shape[0],))

    def loss(fwd):
        def L(x_, raw_, t_):
            y, ld = fwd(x_, raw_, t_)
            return jnp.sum(y * gy) + jnp.sum(ld * gld)

        return jax.grad(L, argnums=(0, 1, 2))

    g_k = loss(fused_coupling_fwd)(x, raw, t)
    g_ref = loss(coupling_fwd_ref)(x, raw, t)
    for a, b, name in zip(g_k, g_ref, ("gx", "graw", "gt")):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4, err_msg=name
        )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m", [300, 96, 28])
def test_coupling_kernel_dtype_ragged_parity(m, dtype):
    """Forward/backward coupling kernels at non-power-of-two spatial extents
    in both dtypes, against the oracle, with per-dtype tolerances."""
    shape = (2, m, 5)
    ks = jax.random.split(RNG, 5)
    x = jax.random.normal(ks[0], shape, dtype)
    raw = jax.random.normal(ks[1], shape, dtype)
    t = jax.random.normal(ks[2], shape, dtype)
    gy = jax.random.normal(ks[3], shape, dtype)
    gld = jax.random.normal(ks[4], (shape[0],))
    bm = pick_block_m(m)
    assert m % bm == 0
    y, ld = fused_coupling_fwd(x, raw, t, block_m=bm)
    y_ref, ld_ref = coupling_fwd_ref(x, raw, t)
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(y_ref, np.float32), **_tol(dtype)
    )
    np.testing.assert_allclose(np.asarray(ld), np.asarray(ld_ref), rtol=1e-3, atol=1e-3)
    out_k = fused_coupling_bwd(y, raw, t, gy, gld, block_m=bm)
    out_ref = coupling_bwd_ref(y, raw, t, gy, gld)
    for a, b, name in zip(out_k, out_ref, ("x", "gx", "graw", "gt")):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            **_tol(dtype), err_msg=f"{name} (m={m}, {dtype.__name__})",
        )


def test_pick_block_m():
    assert pick_block_m(512) == 256
    assert pick_block_m(600) == 200  # largest 8-aligned divisor <= 256
    assert pick_block_m(300) == 300  # no 8-aligned divisor: one block
    assert pick_block_m(97) == 97    # m <= target: one block
    assert pick_block_m(509) == 509  # prime > target: one block
    for m in (64, 300, 509, 600, 1024, 77, 576, 1200):
        b = pick_block_m(m)
        assert m % b == 0
        # the TPU tiling rule: 8-aligned, or the whole axis
        assert (b % 8 == 0 and b <= 256) or b == m


@pytest.mark.parametrize("m", [300, 384])
def test_coupling_kernel_ragged_m(m):
    """Ragged flattened-spatial sizes must not degenerate to one giant block
    (or trip the divisibility assert) — the wrapper picks a legal divisor."""
    shape = (2, m, 4)
    ks = jax.random.split(RNG, 3)
    y = jax.random.normal(ks[0], shape)
    raw = jax.random.normal(ks[1], shape)
    t = jax.random.normal(ks[2], shape)
    bm = pick_block_m(m)
    # 384 tiles in 8-aligned blocks; no multiple of 8 divides 300, so the
    # only block the TPU accepts is the whole axis
    assert bm == (m if m % 8 else 192)
    x = fused_coupling_inv(y, raw, t, block_m=bm)
    np.testing.assert_allclose(
        np.asarray(x), np.asarray(coupling_inv_ref(y, raw, t)), rtol=1e-5, atol=1e-5
    )
    y2, ld = fused_coupling_fwd(x, raw, t, block_m=bm)
    y_ref, ld_ref = coupling_fwd_ref(x, raw, t)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y_ref), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(ld), np.asarray(ld_ref), rtol=1e-4, atol=1e-4)


def test_affine_coupling_kernel_ragged_spatial():
    """AffineCoupling's kernel paths handle non-2^k spatial extents end-to-end
    (flattened m = 5*6 = 30, then a 300-position case exercising the divisor
    search through the layer wrapper)."""
    from repro.core.coupling import AffineCoupling
    from repro.nn.nets import CouplingMLP

    factory = lambda d_out: CouplingMLP(d_out, hidden=8, depth=1)
    for spatial in ((5, 6), (300,)):
        layer_ref = AffineCoupling(factory)
        layer_k = AffineCoupling(factory, kernel_inverse=True, kernel_training=True)
        x = jax.random.normal(RNG, (2,) + spatial + (6,))
        params = layer_ref.init(jax.random.PRNGKey(1), x)
        y_ref, ld_ref = layer_ref.forward(params, x)
        y_k, ld_k = layer_k.forward(params, x)
        np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_ref), rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(ld_k), np.asarray(ld_ref), rtol=1e-4, atol=1e-4)
        x2 = layer_k.inverse(params, y_k)
        np.testing.assert_allclose(np.asarray(x2), np.asarray(x), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# conv1x1
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(2, 256, 12), (1, 512, 48), (2, 128, 192), (1, 300, 8)])
def test_conv1x1_kernel(shape, dtype):
    b, m, c = shape
    x = jax.random.normal(RNG, shape, dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (c, c), jnp.float32)
    y = invertible_conv1x1(x, w, block_m=128)
    y_ref = conv1x1_mm_ref(x, w)
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(y_ref, np.float32), **_tol(dtype)
    )


@pytest.mark.parametrize("m", [256, 300])
def test_conv1x1_custom_vjp_matches_autodiff(m):
    """gx = gy @ W^T and the VMEM-accumulated gW = sum x^T gy match plain AD
    through the oracle (acceptance: <= 1e-4); m=300 exercises the ragged
    block_m divisor pick in the VJP wrappers."""
    b, c = 2, 12
    x = jax.random.normal(RNG, (b, m, c))
    w = jax.random.normal(jax.random.PRNGKey(1), (c, c))
    gy = jax.random.normal(jax.random.PRNGKey(2), (b, m, c))

    def loss(mm):
        return jax.grad(lambda x_, w_: jnp.sum(mm(x_, w_) * gy), argnums=(0, 1))

    g_k = loss(invertible_conv1x1)(x, w)
    g_ref = loss(conv1x1_mm_ref)(x, w)
    for a, b_, name in zip(g_k, g_ref, ("gx", "gw")):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), rtol=1e-4, atol=1e-4, err_msg=name
        )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m", [300, 96, 28])
def test_conv1x1_kernel_dtype_ragged_parity(m, dtype):
    """conv1x1_mm forward + VJP at non-power-of-two extents in both dtypes;
    the (C, C) gW accumulator stays f32 so bf16 activations keep a tight
    weight-gradient tolerance."""
    b, c = 2, 8
    x = jax.random.normal(RNG, (b, m, c), dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (c, c), jnp.float32)
    gy = jax.random.normal(jax.random.PRNGKey(2), (b, m, c), dtype)
    y = invertible_conv1x1(x, w)
    y_ref = conv1x1_mm_ref(x, w)
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(y_ref, np.float32), **_tol(dtype)
    )

    def loss(mm):
        return jax.grad(
            lambda x_, w_: jnp.sum(mm(x_, w_).astype(jnp.float32) * gy.astype(jnp.float32)),
            argnums=(0, 1),
        )

    g_k = loss(invertible_conv1x1)(x, w)
    g_ref = loss(conv1x1_mm_ref)(x, w)
    gw_tol = dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=1e-4, atol=1e-4)
    for a, b_, name, tol in zip(g_k, g_ref, ("gx", "gw"), (_tol(dtype), gw_tol)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b_, np.float32), **tol,
            err_msg=f"{name} (m={m}, {dtype.__name__})",
        )


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "shape",  # (B, Hq, Hkv, S, D)
    [(1, 4, 4, 256, 32), (2, 8, 2, 256, 64), (1, 6, 1, 512, 64)],
)
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention(shape, dtype, causal):
    b, hq, hkv, s, d = shape
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (b, hq, s, d), dtype)
    k = jax.random.normal(ks[1], (b, hkv, s, d), dtype)
    v = jax.random.normal(ks[2], (b, hkv, s, d), dtype)
    o = flash_sdpa(q, k, v, causal=causal, block_q=128, block_k=128)
    o_ref = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(o_ref, np.float32), **_tol(dtype)
    )


# ---------------------------------------------------------------------------
# ssd
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(1, 2, 256, 16, 16), (2, 4, 128, 32, 16)])
def test_ssd_kernel(shape, dtype):
    b, h, s, p, n = shape
    ks = jax.random.split(RNG, 5)
    x = jax.random.normal(ks[0], (b, h, s, p), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, h, s))).astype(jnp.float32)
    da = -dt * jnp.exp(jax.random.normal(ks[2], (b, h, s)) * 0.2)
    b_in = jax.random.normal(ks[3], (b, s, n), dtype)
    c_in = jax.random.normal(ks[4], (b, s, n), dtype)
    y, st = mamba2_ssd(x, da, dt, b_in, c_in, chunk=64)
    y_ref, st_ref = ssd_ref(
        x.astype(jnp.float32), da, dt, b_in.astype(jnp.float32), c_in.astype(jnp.float32)
    )
    tol = dict(rtol=5e-2, atol=5e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(y_ref), **tol)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st_ref), **tol)


def test_ssd_kernel_matches_model_path():
    """The kernel must agree with the model's chunked-scan implementation."""
    from repro.nn.ssm import _ssd_chunk_scan

    b, h, s, p, n = 2, 3, 128, 16, 16
    ks = jax.random.split(RNG, 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    da = -dt * 0.5
    b_in = jax.random.normal(ks[3], (b, s, n))
    c_in = jax.random.normal(ks[4], (b, s, n))
    y_model, st_model = _ssd_chunk_scan(
        x, da, dt, b_in, c_in, jnp.zeros((b, h, p, n)), chunk=32
    )
    y_k, st_k = mamba2_ssd(
        x.transpose(0, 2, 1, 3), da.transpose(0, 2, 1), dt.transpose(0, 2, 1),
        b_in, c_in, chunk=32,
    )
    np.testing.assert_allclose(
        np.asarray(y_k), np.asarray(y_model.transpose(0, 2, 1, 3)), rtol=2e-4, atol=2e-4
    )
    np.testing.assert_allclose(np.asarray(st_k), np.asarray(st_model), rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# rwkv
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(1, 2, 128, 16), (2, 4, 64, 32)])
def test_rwkv_kernel(shape, dtype):
    b, h, s, kdim = shape
    ks = jax.random.split(RNG, 5)
    r = jax.random.normal(ks[0], (b, h, s, kdim), dtype)
    k = jax.random.normal(ks[1], (b, h, s, kdim), dtype)
    v = jax.random.normal(ks[2], (b, h, s, kdim), dtype)
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (b, h, s, kdim))).astype(dtype)
    u = (0.1 * jax.random.normal(ks[4], (h, kdim))).astype(jnp.float32)
    y, st = rwkv6_wkv(r, k, v, w, u, chunk=32)
    y_ref, st_ref = wkv_ref(r, k, v, w, u)
    tol = dict(rtol=5e-2, atol=5e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), **tol)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st_ref), **tol)


def test_rwkv_kernel_matches_model_path():
    from repro.nn.ssm import _wkv_scan

    b, h, s, kdim = 2, 3, 64, 16
    ks = jax.random.split(RNG, 5)
    r, k, v = (jax.random.normal(ks[i], (b, s, h, kdim)) for i in range(3))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (b, s, h, kdim)))
    u = 0.1 * jax.random.normal(ks[4], (h, kdim))
    y_model, st_model = _wkv_scan(r, k, v, w, u, jnp.zeros((b, h, kdim, kdim)))
    y_k, st_k = rwkv6_wkv(
        *(t.transpose(0, 2, 1, 3) for t in (r, k, v, w)), u, chunk=32
    )
    np.testing.assert_allclose(
        np.asarray(y_k), np.asarray(y_model.transpose(0, 2, 1, 3)), rtol=2e-4, atol=2e-4
    )
    np.testing.assert_allclose(np.asarray(st_k), np.asarray(st_model), rtol=2e-4, atol=2e-4)


def test_kernel_inverse_integrates_with_glow():
    """GLOW sampling through the fused Pallas coupling kernel matches the
    XLA inverse path (kernel integration test)."""
    from repro.core import build_glow

    rng = jax.random.PRNGKey(3)
    x = jax.random.normal(rng, (2, 8, 8, 3))
    flow_ref = build_glow(n_scales=2, k_steps=2, hidden=8)
    flow_k = build_glow(n_scales=2, k_steps=2, hidden=8, kernel_inverse=True)
    params = flow_ref.init(rng, x)
    z, _ = flow_ref.forward(params, x)
    x_ref = flow_ref.inverse(params, z)
    x_k = flow_k.inverse(params, z)
    np.testing.assert_allclose(
        np.asarray(x_k), np.asarray(x_ref), rtol=1e-4, atol=1e-4
    )


def test_flash_impl_integrates_with_attention_op():
    """attn_apply(impl='flash') must match the XLA einsum path (the model's
    hot-path kernel switch for TPU serving/prefill)."""
    from repro.config import AttentionConfig
    from repro.nn.attention import attn_apply, attn_init

    cfg = AttentionConfig(n_heads=4, n_kv_heads=2, head_dim=32)
    d_model = 64
    params = attn_init(jax.random.PRNGKey(0), d_model, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 128, d_model))
    pos = jnp.arange(128)
    out_xla, _ = attn_apply(params, x, cfg, pos, impl="xla")
    out_flash, _ = attn_apply(params, x, cfg, pos, impl="flash")
    np.testing.assert_allclose(
        np.asarray(out_flash), np.asarray(out_xla), rtol=2e-4, atol=2e-4
    )
