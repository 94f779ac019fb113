"""Fused affine-coupling kernel.

Computes, in one VMEM pass over the transformed half:

    log_s = clamp * tanh(raw / clamp)
    y     = x * exp(log_s) + t          (forward)   or
    x     = (y - t) * exp(-log_s)       (inverse)
    ld[b] += sum(log_s over this tile)  (per-sample logdet accumulation)

plus a fused *backward* (``coupling_bwd``) that reconstructs ``x`` from the
output and emits all cotangents (``gx``, ``graw``, ``gt``) in the same tile
visit — the reversible-VJP training hot path (EXPERIMENTS.md §Perf/H1).

The unfused XLA path materializes log_s, exp(log_s) and the product as
separate HBM tensors; fusing them is the flow-training hot spot (the
conditioner conv/matmul is left to the MXU via regular XLA).

Layout: inputs are viewed as (B, M, C) — batch, flattened spatial positions,
transformed channels.  Grid is (B, M // block_m); the logdet output block
depends only on ``b``, so successive ``m`` steps accumulate into it (TPU
grid iteration is sequential over the trailing axis).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import per_batch_shape, per_batch_spec, resolve_interpret


def _fwd_kernel(x_ref, raw_ref, t_ref, y_ref, ld_ref, *, clamp: float):
    m = pl.program_id(1)
    raw = raw_ref[...].astype(jnp.float32)
    log_s = clamp * jnp.tanh(raw / clamp)
    x = x_ref[...].astype(jnp.float32)
    t = t_ref[...].astype(jnp.float32)
    y_ref[...] = (x * jnp.exp(log_s) + t).astype(y_ref.dtype)

    @pl.when(m == 0)
    def _init():
        ld_ref[...] = jnp.zeros_like(ld_ref)

    ld_ref[...] += jnp.sum(log_s)   # every lane of the (1, 1, LANES) row


def _inv_kernel(y_ref, raw_ref, t_ref, x_ref, *, clamp: float):
    raw = raw_ref[...].astype(jnp.float32)
    log_s = clamp * jnp.tanh(raw / clamp)
    y = y_ref[...].astype(jnp.float32)
    t = t_ref[...].astype(jnp.float32)
    x_ref[...] = ((y - t) * jnp.exp(-log_s)).astype(x_ref.dtype)


def _bwd_kernel(
    y_ref, raw_ref, t_ref, gy_ref, gld_ref, x_ref, gx_ref, graw_ref, gt_ref,
    *, clamp: float
):
    """Fused reversible backward: one VMEM pass reconstructs the input half
    AND emits every cotangent of the affine transform.

        th     = tanh(raw / clamp);  log_s = clamp * th
        x      = (y - t) * exp(-log_s)                      (reconstruction)
        gx     = gy * exp(log_s)
        gt     = gy
        graw   = (gy * x * exp(log_s) + gld[b]) * (1 - th^2)

    The ``gld[b]`` term folds the logdet cotangent in (d logdet / d log_s = 1
    per element); ``1 - th^2 = sech^2(raw/clamp)`` is d log_s / d raw.
    """
    th = jnp.tanh(raw_ref[...].astype(jnp.float32) / clamp)
    log_s = clamp * th
    e_s = jnp.exp(log_s)
    y = y_ref[...].astype(jnp.float32)
    t = t_ref[...].astype(jnp.float32)
    gy = gy_ref[...].astype(jnp.float32)
    gld = gld_ref[pl.program_id(0)]
    x = (y - t) * jnp.exp(-log_s)
    x_ref[...] = x.astype(x_ref.dtype)
    gx_ref[...] = (gy * e_s).astype(gx_ref.dtype)
    graw_ref[...] = ((gy * x * e_s + gld) * (1.0 - th * th)).astype(graw_ref.dtype)
    gt_ref[...] = gy.astype(gt_ref.dtype)


def _grid_specs(b, m, c, block_m):
    grid = (b, m // block_m)
    tile = pl.BlockSpec((1, block_m, c), lambda i, j: (i, j, 0))
    return grid, tile


@functools.partial(jax.jit, static_argnames=("clamp", "block_m", "interpret"))
def coupling_fwd(x, raw, t, *, clamp: float = 2.0, block_m: int = 256,
                 interpret: bool | None = None):
    """x, raw, t: (B, M, C) -> (y: (B, M, C), logdet: (B,))."""
    b, m, c = x.shape
    block_m = min(block_m, m)
    assert m % block_m == 0, (m, block_m)
    grid, tile = _grid_specs(b, m, c, block_m)
    y, ld = pl.pallas_call(
        functools.partial(_fwd_kernel, clamp=clamp),
        grid=grid,
        in_specs=[tile, tile, tile],
        out_specs=[tile, per_batch_spec()],  # ld[b]: accumulated over j
        out_shape=[
            jax.ShapeDtypeStruct((b, m, c), x.dtype),
            per_batch_shape(b),
        ],
        interpret=resolve_interpret(interpret),
    )(x, raw, t)
    return y, ld[:, 0, 0]


@functools.partial(jax.jit, static_argnames=("clamp", "block_m", "interpret"))
def coupling_bwd(y, raw, t, gy, gld, *, clamp: float = 2.0, block_m: int = 256,
                 interpret: bool | None = None):
    """Backward from the *output*: ``(y, raw, t, gy, gld)`` -> ``(x, gx, graw, gt)``.

    y, raw, t, gy: (B, M, C); gld: (B,) logdet cotangent (f32).
    Residuals never include the layer input — ``x`` is reconstructed in VMEM.
    """
    b, m, c = y.shape
    block_m = min(block_m, m)
    assert m % block_m == 0, (m, block_m)
    grid, tile = _grid_specs(b, m, c, block_m)
    x, gx, graw, gt = pl.pallas_call(
        functools.partial(_bwd_kernel, clamp=clamp),
        grid=grid,
        in_specs=[
            tile, tile, tile, tile,
            pl.BlockSpec(memory_space=pltpu.SMEM),  # gld: (B,) scalars
        ],
        out_specs=[tile, tile, tile, tile],
        out_shape=[
            jax.ShapeDtypeStruct((b, m, c), y.dtype),    # x (reconstructed)
            jax.ShapeDtypeStruct((b, m, c), y.dtype),    # gx
            jax.ShapeDtypeStruct((b, m, c), raw.dtype),  # graw
            jax.ShapeDtypeStruct((b, m, c), t.dtype),    # gt
        ],
        interpret=resolve_interpret(interpret),
    )(y, raw, t, gy, gld.astype(jnp.float32))
    return x, gx, graw, gt


@functools.partial(jax.jit, static_argnames=("clamp", "block_m", "interpret"))
def coupling_inv(y, raw, t, *, clamp: float = 2.0, block_m: int = 256,
                 interpret: bool | None = None):
    b, m, c = y.shape
    block_m = min(block_m, m)
    assert m % block_m == 0, (m, block_m)
    grid, tile = _grid_specs(b, m, c, block_m)
    return pl.pallas_call(
        functools.partial(_inv_kernel, clamp=clamp),
        grid=grid,
        in_specs=[tile, tile, tile],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((b, m, c), y.dtype),
        interpret=resolve_interpret(interpret),
    )(y, raw, t)
