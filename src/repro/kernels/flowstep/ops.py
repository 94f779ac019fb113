"""Public wrappers for the fused flow-step megakernel.

Dispatch follows ``kernels.common.kernel_path()``:

* ``compiled`` / ``interpret`` — the Pallas kernels, with ``block_m`` chosen
  from the operands' shape (``flowstep.lane_tiling``) unless given.
* ``reference`` (CPU default) — the jnp oracle, XLA-fused; identical math,
  no interpret-mode emulation tax.

Every operand is channel-major (B, C, M) (see ``flowstep``).
``fused_flowstep_fwd`` carries a ``jax.custom_vjp`` on the Pallas path whose
backward is the two fused kernels (``coupling_half_bwd`` + ``spine_bwd``)
sandwiching nothing: ``h`` is an *input* here, so the conditioner — the XLA
island — composes outside via the chain rule.  Residuals are the output side
only; both intermediates (the conv input and the conv output) are
reconstructed in VMEM during the backward.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.common import kernel_path, resolve_interpret
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _fwd_pallas(x, an_log_s, an_b, w, h, clamp, block_m, interpret):
    return flowstep_fwd(
        x, an_log_s, an_b, w, h, clamp=clamp, block_m=block_m, interpret=interpret,
    )


def _fwd_pallas_fwd(x, an_log_s, an_b, w, h, clamp, block_m, interpret):
    y, ld = flowstep_fwd(
        x, an_log_s, an_b, w, h, clamp=clamp, block_m=block_m, interpret=interpret,
    )
    # residuals are the *output* side only; x1/x2 are reconstructed in VMEM
    return (y, ld), (y, h, an_log_s, an_b, w)


def _fwd_pallas_bwd(clamp, block_m, interpret, res, cts):
    y, h, an_log_s, an_b, w = res
    gy, gld = cts
    x2, gh, gx2 = coupling_half_bwd(
        y, h, gy, gld, clamp=clamp, block_m=block_m, interpret=interpret,
    )
    b, c, m = y.shape
    no_conditioner = jnp.zeros((b, c - h.shape[1] // 2, m), gx2.dtype)
    w_inv = jnp.linalg.inv(w.astype(jnp.float32))
    _x, gx, gw, g_ls, g_b = spine_bwd(
        x2, gx2, no_conditioner, w, w_inv, an_log_s, an_b, block_m=block_m,
        interpret=interpret,
    )
    return (
        gx,
        g_ls.astype(an_log_s.dtype),
        g_b.astype(an_b.dtype),
        gw.astype(w.dtype),
        gh,
    )


_fwd_pallas.defvjp(_fwd_pallas_fwd, _fwd_pallas_bwd)


def fused_flowstep_fwd(x, an_log_s, an_b, w, h, clamp: float = 2.0,
                       block_m: int | None = None):
    """One flow step (actnorm → conv1x1 → coupling) given the conditioner's
    output ``h``: (B, C, M) -> (y, ld_coupling).  Differentiable on every
    path."""
    if kernel_path() == "reference":
        return flowstep_fwd_ref(x, an_log_s, an_b, w, h, clamp=clamp)
    return _fwd_pallas(
        x, an_log_s, an_b, w, h, clamp, block_m, resolve_interpret(None)
    )


def fused_flowstep_inv(y, an_log_s, an_b, w_inv, h, clamp: float = 2.0,
                       block_m: int | None = None):
    """Inverse flow step given ``W^-1`` (sampling path)."""
    if kernel_path() == "reference":
        return flowstep_inv_ref(y, an_log_s, an_b, w_inv, h, clamp=clamp)
    return flowstep_inv(
        y, an_log_s, an_b, w_inv, h, clamp=clamp, block_m=block_m,
        interpret=resolve_interpret(None),
    )


def fused_coupling_half_bwd(y, h, gy, gld, clamp: float = 2.0,
                            block_m: int | None = None):
    """Stage 1 of the flow-step backward: the coupling half.

    ``(x2, gh, gx2)`` from the output side; ``gh`` feeds the conditioner VJP
    (the XLA island between the two fused kernels).
    """
    if kernel_path() == "reference":
        return coupling_half_bwd_ref(y, h, gy, gld, clamp=clamp)
    return coupling_half_bwd(
        y, h, gy, gld, clamp=clamp, block_m=block_m,
        interpret=resolve_interpret(None),
    )


def fused_spine_bwd(x2, gx2, gxb, w, w_inv, an_log_s, an_b,
                    block_m: int | None = None):
    """Stage 2 of the flow-step backward: the conditioner's input cotangent
    ``gxb`` joins ``gx2``, then the fused conv1x1+actnorm reversible backward
    — ``(x, gx, gw, g_log_s, g_b)`` in one VMEM pass."""
    if kernel_path() == "reference":
        return spine_bwd_ref(x2, gx2, gxb, w, w_inv, an_log_s, an_b)
    return spine_bwd(
        x2, gx2, gxb, w, w_inv, an_log_s, an_b, block_m=block_m,
        interpret=resolve_interpret(None),
    )
