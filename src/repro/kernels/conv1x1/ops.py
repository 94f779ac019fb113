"""Public wrapper for the 1x1-conv kernel, with a custom VJP.

The backward reuses the same VMEM-resident-W layout in both directions:
``gx = gy @ W^T`` is the forward kernel applied to the transposed weight, and
``gW = sum_{b,m} x^T gy`` streams position tiles against a (C, C) accumulator
that never leaves VMEM (``conv1x1_gw``).

Execution dispatch mirrors the coupling/flowstep wrappers
(``kernels.common.kernel_path()``): compiled Pallas on TPU with the
``block_m`` autotuner, the jnp oracle off-TPU, interpret only when forced —
with the interpret flag resolved eagerly and threaded through the custom VJP
as a static argument.
"""

from __future__ import annotations

import functools

import jax

from repro.kernels.common import (
    kernel_path,
    resolve_block_m,
    resolve_interpret,
    time_candidate,
)
from repro.kernels.conv1x1.conv1x1 import conv1x1_gw, conv1x1_mm
from repro.kernels.conv1x1.ref import conv1x1_gw_ref, conv1x1_mm_ref


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _mm_pallas(x, w, block_m, interpret):
    return conv1x1_mm(x, w, block_m=block_m, interpret=interpret)


def _conv_fwd(x, w, block_m, interpret):
    y = conv1x1_mm(x, w, block_m=block_m, interpret=interpret)
    return y, (x, w)


def _conv_bwd(block_m, interpret, res, gy):
    x, w = res
    gx = conv1x1_mm(gy, w.T, block_m=block_m, interpret=interpret)
    gw = conv1x1_gw(x, gy, block_m=block_m, interpret=interpret)
    return gx, gw.astype(w.dtype)


_mm_pallas.defvjp(_conv_fwd, _conv_bwd)


def _measure_mm(x, w):
    def run(bm):
        return time_candidate(
            lambda: conv1x1_mm(x, w, block_m=bm, interpret=False)
        )

    return run


@jax.custom_vjp
def _mm_reference(x, w):
    return conv1x1_mm_ref(x, w)


def _mm_reference_fwd(x, w):
    return conv1x1_mm_ref(x, w), (x, w)


def _mm_reference_bwd(res, gy):
    x, w = res
    gx = conv1x1_mm_ref(gy, w.T)
    return gx, conv1x1_gw_ref(x, gy).astype(w.dtype)


_mm_reference.defvjp(_mm_reference_fwd, _mm_reference_bwd)


def invertible_conv1x1(x, w, block_m: int | None = None):
    """x: (B, M, C); w: (C, C) -> (B, M, C), differentiable on every path."""
    if kernel_path() == "reference":
        # same custom-VJP structure as the kernel path so gradients match
        # bit-for-bit in structure (f32-accumulated gW) across backends
        return _mm_reference(x, w)
    bm = resolve_block_m("conv1x1_mm", x, block_m, measure=_measure_mm(x, w))
    return _mm_pallas(x, w, bm, resolve_interpret(None))
