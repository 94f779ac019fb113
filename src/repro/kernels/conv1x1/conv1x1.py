"""Invertible 1x1 convolution kernel: channel-mixing matmul on the MXU.

``y[b, m, :] = x[b, m, :] @ W`` for W (C, C).  After GLOW's multiscale
squeezes C reaches 48-768 — small against the 128x128 MXU tile, so the
winning layout streams large position tiles (block_m rows) against a fully
VMEM-resident W, rather than tiling W.  f32 accumulation via
``preferred_element_type``, at full f32 precision on the MXU (the layer must
invert to f32 accuracy).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import resolve_interpret


def _kernel(x_ref, w_ref, y_ref):
    x = x_ref[...]
    w = w_ref[...].astype(x.dtype)
    y = jax.lax.dot_general(
        x[0], w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    y_ref[...] = y[None].astype(y_ref.dtype)


def _gw_kernel(x_ref, gy_ref, gw_ref):
    b = pl.program_id(0)
    m = pl.program_id(1)

    @pl.when((b == 0) & (m == 0))
    def _init():
        gw_ref[...] = jnp.zeros_like(gw_ref)

    x = x_ref[...].astype(jnp.float32)
    gy = gy_ref[...].astype(jnp.float32)
    gw_ref[...] += jax.lax.dot_general(
        x[0], gy[0], (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def conv1x1_gw(x, gy, *, block_m: int = 256, interpret: bool | None = None):
    """Weight cotangent ``gW = sum_{b,m} x[b,m,:]^T gy[b,m,:]`` -> (C, C) f32.

    Same layout as the forward: position tiles stream through VMEM while the
    (C, C) accumulator stays resident (grid iteration is sequential on TPU,
    so successive steps accumulate into the single output block).
    """
    b, m, c = x.shape
    block_m = min(block_m, m)
    assert m % block_m == 0, (m, block_m)
    return pl.pallas_call(
        _gw_kernel,
        grid=(b, m // block_m),
        in_specs=[
            pl.BlockSpec((1, block_m, c), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_m, c), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((c, c), lambda i, j: (0, 0)),  # accumulated
        out_shape=jax.ShapeDtypeStruct((c, c), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(x, gy)


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def conv1x1_mm(x, w, *, block_m: int = 256, interpret: bool | None = None):
    """x: (B, M, C); w: (C, C) -> (B, M, C)."""
    b, m, c = x.shape
    block_m = min(block_m, m)
    assert m % block_m == 0, (m, block_m)
    return pl.pallas_call(
        _kernel,
        grid=(b, m // block_m),
        in_specs=[
            pl.BlockSpec((1, block_m, c), lambda i, j: (i, j, 0)),
            pl.BlockSpec((c, c), lambda i, j: (0, 0)),  # W resident in VMEM
        ],
        out_specs=pl.BlockSpec((1, block_m, c), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b, m, c), x.dtype),
        interpret=resolve_interpret(interpret),
    )(x, w)
