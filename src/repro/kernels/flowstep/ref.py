"""Pure-jnp oracle for the fused flow-step (actnorm → conv1x1 → coupling)
megakernel.  One source of truth for the math on every path: the Pallas
kernels must match these to <=1e-4, and on CPU the public wrappers execute
these directly (XLA-fused) instead of interpret-mode emulation.

Layout: the (B, M, C) view; ``ca = C // 2`` channels are transformed by the
coupling given the conditioner outputs ``raw``/``t`` (shape (B, M, ca)).
The emitted logdet is the *coupling* contribution only — the actnorm and
1x1-conv logdets are per-batch constants (``spatial * Σ log_s``) the caller
adds outside, where they stay differentiable by plain AD.  Matmuls run at
full f32 precision, as in the kernels.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def flowstep_fwd_ref(x, an_log_s, an_b, w, raw, t, clamp: float = 2.0):
    """(y, ld_coupling): actnorm -> x @ W -> affine-couple the first half."""
    ca = raw.shape[-1]
    x1 = x.astype(jnp.float32) * jnp.exp(an_log_s.astype(jnp.float32)) + an_b.astype(
        jnp.float32
    )
    x2 = jnp.matmul(x1, w.astype(jnp.float32), precision=HIGHEST)
    xa, xb = x2[..., :ca], x2[..., ca:]
    log_s = clamp * jnp.tanh(raw.astype(jnp.float32) / clamp)
    ya = xa * jnp.exp(log_s) + t.astype(jnp.float32)
    y = jnp.concatenate([ya, xb], axis=-1)
    ld = jnp.sum(log_s, axis=(1, 2))
    return y.astype(x.dtype), ld


def flowstep_inv_ref(y, an_log_s, an_b, w_inv, raw, t, clamp: float = 2.0):
    """Exact inverse of :func:`flowstep_fwd_ref` given ``W^-1``."""
    ca = raw.shape[-1]
    ya, yb = y[..., :ca].astype(jnp.float32), y[..., ca:].astype(jnp.float32)
    log_s = clamp * jnp.tanh(raw.astype(jnp.float32) / clamp)
    xa = (ya - t.astype(jnp.float32)) * jnp.exp(-log_s)
    x2 = jnp.concatenate([xa, yb], axis=-1)
    x1 = jnp.matmul(x2, w_inv.astype(jnp.float32), precision=HIGHEST)
    x = (x1 - an_b.astype(jnp.float32)) * jnp.exp(-an_log_s.astype(jnp.float32))
    return x.astype(y.dtype)


def spine_bwd_ref(x2, gx2, w, w_inv, an_log_s, an_b):
    """Fused conv1x1+actnorm backward from the conv *output* side.

    Given the reconstructed conv output ``x2`` and its cotangent ``gx2``
    (which must already include the conditioner's contribution on the
    untransformed lanes), one pass emits:

        x1     = x2 @ W^-1                  (conv input, reconstructed)
        x      = (x1 - b) * exp(-log_s)     (step input, reconstructed)
        gx1    = gx2 @ W^T
        gx     = gx1 * exp(log_s)
        gW     = Σ_{b,m} x1^T gx2           (f32 accumulated)
        g_b    = Σ_{b,m} gx1
        g_logs = Σ_{b,m} gx1 * (x1 - b)     (x * exp(log_s) == x1 - b)

    The logdet cotangents (per-batch constants) are the caller's to add.
    """
    ls32 = an_log_s.astype(jnp.float32)
    b32 = an_b.astype(jnp.float32)
    x2_32 = x2.astype(jnp.float32)
    gx2_32 = gx2.astype(jnp.float32)
    x1 = jnp.matmul(x2_32, w_inv.astype(jnp.float32), precision=HIGHEST)
    x = (x1 - b32) * jnp.exp(-ls32)
    gx1 = jnp.matmul(gx2_32, w.astype(jnp.float32).T, precision=HIGHEST)
    gx = gx1 * jnp.exp(ls32)
    gw = jnp.einsum("bmi,bmj->ij", x1, gx2_32, precision=HIGHEST)
    g_b = jnp.sum(gx1, axis=(0, 1))
    g_log_s = jnp.sum(gx1 * (x1 - b32), axis=(0, 1))
    return x.astype(x2.dtype), gx.astype(x2.dtype), gw, g_log_s, g_b
