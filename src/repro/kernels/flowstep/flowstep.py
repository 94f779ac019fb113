"""Fused flow-step megakernel: actnorm → 1x1-conv → affine coupling.

GLOW's whole flow step executes in **one VMEM residency per block** instead
of three kernel launches with HBM round-trips between the sub-layers:

forward (``flowstep_fwd``), given the conditioner outputs ``raw``/``t``::

    x1    = x * exp(an_log_s) + an_b          (actnorm)
    x2    = x1 @ W                            (1x1 conv; f32 MXU accumulation)
    xa,xb = split(x2, ca)
    y     = [xa * exp(clamp*tanh(raw/clamp)) + t, xb]
    ld[b] += Σ_tile log_s                     (coupling logdet; an/conv logdets
                                               are per-batch constants added by
                                               the caller)

backward spine (``spine_bwd``): the conv+actnorm half of the reversible
backward, fused into one pass — reconstruction of both intermediates AND all
cotangents, with the (C, C) weight-gradient and the per-channel actnorm
gradients accumulated in VMEM across grid steps (TPU grid iteration is
sequential, so successive blocks add into the same output block).  The
coupling half of the backward is ``kernels.coupling.coupling_bwd``; the two
kernels sandwich the conditioner VJP, which is the unavoidable XLA island
(its 3x3 convs belong on the MXU) — see EXPERIMENTS.md §Perf/H2 for the
fusion-boundary analysis.

Layout: (B, M, C) — batch, flattened spatial, channels; ``raw``/``t`` carry
the transformed half's ``ca = C//2`` channels.  Grid is (B, M // block_m);
per-channel/per-batch accumulator outputs depend only on a prefix of the
grid, so trailing steps accumulate.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: the step must stay invertible to f32 accuracy: the reversible backward
#: rebuilds each input from the output through these matmuls, so they run
#: at full f32 precision on the MXU, not the one-pass bf16 default
HIGHEST = jax.lax.Precision.HIGHEST


def _fwd_kernel(x_ref, ls_ref, b_ref, w_ref, raw_ref, t_ref, y_ref, ld_ref,
                *, clamp: float, ca: int):
    m = pl.program_id(1)
    x = x_ref[...][0].astype(jnp.float32)          # (bm, C)
    ls = ls_ref[...][0].astype(jnp.float32)        # (C,)
    b = b_ref[...][0].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)             # (C, C) VMEM-resident
    x1 = x * jnp.exp(ls) + b
    x2 = jax.lax.dot_general(
        x1, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        precision=HIGHEST,
    )
    xa, xb = x2[:, :ca], x2[:, ca:]
    log_s = clamp * jnp.tanh(raw_ref[...][0].astype(jnp.float32) / clamp)
    ya = xa * jnp.exp(log_s) + t_ref[...][0].astype(jnp.float32)
    y_ref[...] = jnp.concatenate([ya, xb], axis=-1)[None].astype(y_ref.dtype)

    @pl.when(m == 0)
    def _init():
        ld_ref[...] = jnp.zeros_like(ld_ref)

    ld_ref[...] += jnp.sum(log_s)   # every lane of the (1, 1, LANES) row


def _inv_kernel(y_ref, ls_ref, b_ref, winv_ref, raw_ref, t_ref, x_ref,
                *, clamp: float, ca: int):
    y = y_ref[...][0].astype(jnp.float32)
    ls = ls_ref[...][0].astype(jnp.float32)
    b = b_ref[...][0].astype(jnp.float32)
    winv = winv_ref[...].astype(jnp.float32)
    log_s = clamp * jnp.tanh(raw_ref[...][0].astype(jnp.float32) / clamp)
    xa = (y[:, :ca] - t_ref[...][0].astype(jnp.float32)) * jnp.exp(-log_s)
    x2 = jnp.concatenate([xa, y[:, ca:]], axis=-1)
    x1 = jax.lax.dot_general(
        x2, winv, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        precision=HIGHEST,
    )
    x_ref[...] = ((x1 - b) * jnp.exp(-ls))[None].astype(x_ref.dtype)


def _spine_bwd_kernel(x2_ref, gx2_ref, w_ref, winv_ref, ls_ref, b_ref,
                      x_ref, gx_ref, gw_ref, gls_ref, gb_ref):
    i = pl.program_id(0)
    m = pl.program_id(1)
    x2 = x2_ref[...][0].astype(jnp.float32)
    gx2 = gx2_ref[...][0].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    winv = winv_ref[...].astype(jnp.float32)
    ls = ls_ref[...][0].astype(jnp.float32)
    b = b_ref[...][0].astype(jnp.float32)
    x1 = jax.lax.dot_general(            # conv input, reconstructed
        x2, winv, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        precision=HIGHEST,
    )
    gx1 = jax.lax.dot_general(           # gx1 = gx2 @ W^T (contract on cols)
        gx2, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=HIGHEST,
    )
    x_ref[...] = ((x1 - b) * jnp.exp(-ls))[None].astype(x_ref.dtype)
    gx_ref[...] = (gx1 * jnp.exp(ls))[None].astype(gx_ref.dtype)

    @pl.when((i == 0) & (m == 0))
    def _init():
        gw_ref[...] = jnp.zeros_like(gw_ref)
        gls_ref[...] = jnp.zeros_like(gls_ref)
        gb_ref[...] = jnp.zeros_like(gb_ref)

    gw_ref[...] += jax.lax.dot_general(  # gW += x1^T gx2
        x1, gx2, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        precision=HIGHEST,
    )
    gls_ref[...] += jnp.sum(gx1 * (x1 - b), axis=0)[None]
    gb_ref[...] += jnp.sum(gx1, axis=0)[None]


def _specs(b, m, c, ca, block_m):
    grid = (b, m // block_m)
    tile = pl.BlockSpec((1, block_m, c), lambda i, j: (i, j, 0))
    half = pl.BlockSpec((1, block_m, ca), lambda i, j: (i, j, 0))
    chan = pl.BlockSpec((1, c), lambda i, j: (0, 0))      # per-channel params
    mat = pl.BlockSpec((c, c), lambda i, j: (0, 0))       # VMEM-resident C×C
    return grid, tile, half, chan, mat


@functools.partial(jax.jit, static_argnames=("clamp", "block_m", "interpret"))
def flowstep_fwd(x, an_log_s, an_b, w, raw, t, *, clamp: float = 2.0,
                 block_m: int = 256, interpret: bool | None = None):
    """x: (B, M, C); an_*: (C,); w: (C, C); raw, t: (B, M, ca)
    -> (y: (B, M, C), ld_coupling: (B,) f32)."""
    from repro.kernels.common import per_batch_shape, per_batch_spec, resolve_interpret

    b, m, c = x.shape
    ca = raw.shape[-1]
    block_m = min(block_m, m)
    assert m % block_m == 0, (m, block_m)
    grid, tile, half, chan, mat = _specs(b, m, c, ca, block_m)
    y, ld = pl.pallas_call(
        functools.partial(_fwd_kernel, clamp=clamp, ca=ca),
        grid=grid,
        in_specs=[tile, chan, chan, mat, half, half],
        out_specs=[tile, per_batch_spec()],               # ld[b]: accumulated
        out_shape=[
            jax.ShapeDtypeStruct((b, m, c), x.dtype),
            per_batch_shape(b),
        ],
        interpret=resolve_interpret(interpret),
    )(x, an_log_s.reshape(1, c), an_b.reshape(1, c), w, raw, t)
    return y, ld[:, 0, 0]


@functools.partial(jax.jit, static_argnames=("clamp", "block_m", "interpret"))
def flowstep_inv(y, an_log_s, an_b, w_inv, raw, t, *, clamp: float = 2.0,
                 block_m: int = 256, interpret: bool | None = None):
    """Inverse flow step given ``W^-1`` (computed once outside, O(C^3))."""
    from repro.kernels.common import resolve_interpret

    b, m, c = y.shape
    ca = raw.shape[-1]
    block_m = min(block_m, m)
    assert m % block_m == 0, (m, block_m)
    grid, tile, half, chan, mat = _specs(b, m, c, ca, block_m)
    return pl.pallas_call(
        functools.partial(_inv_kernel, clamp=clamp, ca=ca),
        grid=grid,
        in_specs=[tile, chan, chan, mat, half, half],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((b, m, c), y.dtype),
        interpret=resolve_interpret(interpret),
    )(y, an_log_s.reshape(1, c), an_b.reshape(1, c), w_inv, raw, t)


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def spine_bwd(x2, gx2, w, w_inv, an_log_s, an_b, *, block_m: int = 256,
              interpret: bool | None = None):
    """Fused conv1x1+actnorm reversible backward (see module docstring).

    x2, gx2: (B, M, C) -> (x, gx: (B, M, C), gw: (C, C) f32,
    g_log_s, g_b: (C,) f32).  ``gx2`` must already carry the conditioner's
    contribution on the untransformed lanes.
    """
    from repro.kernels.common import resolve_interpret

    b, m, c = x2.shape
    block_m = min(block_m, m)
    assert m % block_m == 0, (m, block_m)
    grid, tile, _half, chan, mat = _specs(b, m, c, c // 2, block_m)
    x, gx, gw, gls, gb = pl.pallas_call(
        _spine_bwd_kernel,
        grid=grid,
        in_specs=[tile, tile, mat, mat, chan, chan],
        out_specs=[tile, tile, mat, chan, chan],      # trailing 3 accumulated
        out_shape=[
            jax.ShapeDtypeStruct((b, m, c), x2.dtype),
            jax.ShapeDtypeStruct((b, m, c), x2.dtype),
            jax.ShapeDtypeStruct((c, c), jnp.float32),
            jax.ShapeDtypeStruct((1, c), jnp.float32),
            jax.ShapeDtypeStruct((1, c), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(x2, gx2, w, w_inv, an_log_s.reshape(1, c), an_b.reshape(1, c))
    return x, gx, gw, gls[0], gb[0]
