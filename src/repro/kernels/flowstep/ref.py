"""Pure-jnp oracle for the fused flow-step (actnorm → conv1x1 → coupling)
megakernel.  One source of truth for the math on every path: the Pallas
kernels must match these to <=1e-4, and on CPU the public wrappers execute
these directly (XLA-fused) instead of interpret-mode emulation.

Layout: channel-major (B, C, M), as in the kernels; per-channel parameters
are (C,).  The conditioner output ``h`` (B, 2*ca, M) holds ``raw`` on its
first ``ca = C // 2`` channels and ``t`` on the rest; the coupling
transforms the first ``ca`` channels of the conv output.  The emitted
logdet is the *coupling* contribution only — the actnorm and 1x1-conv
logdets are per-batch constants (``spatial * Σ log_s``) the caller adds
outside, where they stay differentiable by plain AD.  Matmuls run at full
f32 precision, as in the kernels; float64 inputs give a float64 oracle.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def _f(x):
    """``x`` in the oracle's working precision: at least float32 (float64
    inputs, under ``jax.enable_x64``, stay float64)."""
    return x.astype(jnp.promote_types(x.dtype, F32))


def channel_mix(w, x):
    """The 1x1 conv ``x @ w`` in the (B, C, M) layout: ``out[:, j] =
    Σ_i w[i, j] x[:, i]``, at full f32 precision."""
    return jnp.einsum("ij,bim->bjm", _f(w), _f(x), precision=HIGHEST)


def _col(v):
    return _f(v)[:, None]


def flowstep_fwd_ref(x, an_log_s, an_b, w, h, clamp: float = 2.0):
    """(y, ld_coupling): actnorm -> W^T x -> affine-couple the first half."""
    ca = h.shape[1] // 2
    x2 = channel_mix(w, _f(x) * jnp.exp(_col(an_log_s)) + _col(an_b))
    log_s = clamp * jnp.tanh(_f(h[:, :ca]) / clamp)
    ya = x2[:, :ca] * jnp.exp(log_s) + _f(h[:, ca:])
    y = jnp.concatenate([ya, x2[:, ca:]], axis=1)
    return y.astype(x.dtype), jnp.sum(log_s, axis=(1, 2))


def flowstep_inv_ref(y, an_log_s, an_b, w_inv, h, clamp: float = 2.0):
    """Exact inverse of :func:`flowstep_fwd_ref` given ``W^-1``."""
    ca = h.shape[1] // 2
    y32 = _f(y)
    log_s = clamp * jnp.tanh(_f(h[:, :ca]) / clamp)
    xa = (y32[:, :ca] - _f(h[:, ca:])) * jnp.exp(-log_s)
    x1 = channel_mix(w_inv, jnp.concatenate([xa, y32[:, ca:]], axis=1))
    x = (x1 - _col(an_b)) * jnp.exp(-_col(an_log_s))
    return x.astype(y.dtype)


def coupling_half_bwd_ref(y, h, gy, gld, clamp: float = 2.0):
    """Coupling half of the backward from the output side:
    ``(x2, gh, gx2)`` — the conv output rebuilt, the cotangent of ``h``, and
    the coupling's part of the conv output's cotangent (the conditioner's
    part, on channels ``ca:``, is :func:`spine_bwd_ref`'s ``gxb``)."""
    ca = h.shape[1] // 2
    y32, gy32 = _f(y), _f(gy)
    th = jnp.tanh(_f(h[:, :ca]) / clamp)
    log_s = clamp * th
    e_s = jnp.exp(log_s)
    gya = gy32[:, :ca]
    xa = (y32[:, :ca] - _f(h[:, ca:])) * jnp.exp(-log_s)
    graw = (gya * xa * e_s + _f(gld)[:, None, None]) * (1.0 - th * th)
    x2 = jnp.concatenate([xa, y32[:, ca:]], axis=1)
    gh = jnp.concatenate([graw, gya], axis=1)
    gx2 = jnp.concatenate([gya * e_s, gy32[:, ca:]], axis=1)
    return x2.astype(y.dtype), gh.astype(h.dtype), gx2.astype(gy.dtype)


def spine_bwd_ref(x2, gx2, gxb, w, w_inv, an_log_s, an_b):
    """Fused conv1x1+actnorm backward from the conv *output* side.

    Given the rebuilt conv output ``x2``, the coupling's part of its
    cotangent ``gx2`` and the conditioner's part ``gxb`` (on the last
    ``C - ca`` channels), one pass emits:

        gx2    = gx2 + [0; gxb]
        x1     = W^-T x2                    (conv input, reconstructed)
        x      = (x1 - b) * exp(-log_s)     (step input, reconstructed)
        gx1    = W gx2
        gx     = gx1 * exp(log_s)
        gW     = Σ_{b,m} x1 gx2^T           (f32 accumulated)
        g_b    = Σ_{b,m} gx1
        g_logs = Σ_{b,m} gx1 * (x1 - b)     (x * exp(log_s) == x1 - b)

    The logdet cotangents (per-batch constants) are the caller's to add.
    """
    ca = x2.shape[1] - gxb.shape[1]
    ls, b = _col(an_log_s), _col(an_b)
    g32 = _f(gx2)
    g32 = jnp.concatenate([g32[:, :ca], g32[:, ca:] + _f(gxb)], axis=1)
    x1 = channel_mix(w_inv, x2)
    gx1 = channel_mix(_f(w).T, g32)
    x = (x1 - b) * jnp.exp(-ls)
    gx = gx1 * jnp.exp(ls)
    gw = jnp.einsum("bim,bjm->ij", x1, g32, precision=HIGHEST)
    g_b = jnp.sum(gx1, axis=(0, 2))
    g_log_s = jnp.sum(gx1 * (x1 - b), axis=(0, 2))
    return x.astype(x2.dtype), gx.astype(x2.dtype), gw, g_log_s, g_b
