"""Memory-frugal backpropagation through invertible layer stacks.

This module is the reproduction of the paper's central mechanism: instead of
letting reverse-mode AD store every intermediate activation, the backward pass
*reconstructs* each layer's input from its output via the layer inverse, then
differentiates that single layer locally.  Only the network **output** crosses
the forward/backward boundary, so peak activation memory is independent of
depth (paper Fig. 2) and inputs can grow far past the naive-AD OOM point
(paper Fig. 1).

Two engines are provided:

* ``make_chain_apply`` — heterogeneous chains (a python tuple of ``Invertible``
  layers; used by the flow networks: GLOW, RealNVP, HINT, ...).
* ``make_scan_apply`` — homogeneous stacks with layer-stacked parameters,
  driven by ``lax.scan`` in both directions.  HLO size is O(1) in depth (so
  XLA compile time is flat) and this is the production path for reversible
  transformer LMs.

Both take a ``grad_mode``:

* ``"invertible"`` — the paper's technique (custom VJP, recompute by inversion).
* ``"coupled"``    — fused reversible backward (EXPERIMENTS.md §Perf/H1).  In
  the chain engine, layers that expose ``fused_bwd(params, y, gy, gld, cond)
  -> (x, gx, gparams, gcond)`` hand-fuse the inverse reconstruction with the
  local VJP so each sub-network (coupling conditioner) is evaluated **once**
  in the backward instead of twice (~4/3 forward-equivalents of compute vs
  the generic 5/3); layers without the hook fall back to the generic
  invert-then-vjp step.  The whole zoo implements the hook — couplings
  (``AffineCoupling``, recursive ``HINTCoupling``) backed by the Pallas
  coupling-backward kernel, ``Conv1x1`` (LU-aware hand backward),
  ``ActNorm`` (closed form), the squeezes (orthonormal/permutation
  transpose == inverse), ``HyperbolicLayer`` (leapfrog transpose), the
  multiscale ``Split``/``Pack`` state wrappers, and ``InvertibleChain``
  itself (nested chains reuse :func:`chain_backward`, so inner layers stay
  fused) — see the conformance matrix in EXPERIMENTS.md and the engagement
  probe in ``tests/test_conformance.py``.  In the scan engine the same
  contract is provided per-step via ``step_bwd``.
* ``"autodiff"``   — identical math through plain ``jax.grad``; the stand-in
  for the PyTorch/``normflows`` baseline the paper compares against.
* ``"remat"``      — (scan engine) classic gradient checkpointing on the layer
  body: stores one carry per layer, recomputes internals.  An extra baseline
  the paper alludes to ("checkpointing-style"), strictly worse than
  ``"invertible"`` in memory.

The local per-layer differentiation uses ordinary ``jax.vjp``, so arbitrary
non-invertible sub-networks (coupling conditioners, summary networks) are
AD'd automatically — the JAX analogue of the package's ChainRules integration.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.types import Invertible, PyTree

GRAD_MODES = ("invertible", "coupled", "autodiff", "remat")


def _tree_add(a, b):
    if a is None:
        return b
    if b is None:
        return a
    return jax.tree_util.tree_map(jnp.add, a, b)


def _stop(x):
    return jax.tree_util.tree_map(lax.stop_gradient, x)


def psum_cotangents(tree, axis: Optional[str]):
    """Reduce *replicated-input* cotangents over a mapped mesh axis.

    Under ``shard_map`` data parallelism (``repro.dist``) the params (and
    the scan engine's shared ``extra`` pytree) are replicated while ``x``
    is batch-sharded, so each device's backward produces only its shard's
    contribution to ``gparams`` — including the fused kernels' ``gW`` and
    actnorm accumulators.  A single ``lax.psum`` over the data axis at the
    VJP boundary makes the custom VJP SPMD-correct (grad-identical to the
    single-device backward up to f32 reduction order).  Batch-aligned
    inputs (``x``, and the chain engine's per-example ``cond``) must NOT
    pass through here — their cotangents are per-shard by construction.
    ``float0`` cotangents (integer permutation/sign buffers) and ``None``
    subtrees pass through untouched.  Outside any mapping of the axis
    (plain single-device differentiation of the same flow) the reduction
    is a no-op, so one flow object serves both contexts.
    """
    if axis is None or tree is None:
        return tree

    def red(v):
        if v is None or getattr(v, "dtype", None) == jax.dtypes.float0:
            return v
        with jax.named_scope("grad_reduce"):
            return lax.psum(v, axis)

    try:
        return jax.tree_util.tree_map(red, tree, is_leaf=lambda v: v is None)
    except NameError:  # axis unbound: not under shard_map/pmap of `axis`
        return tree


def _zero_logdet(x: PyTree) -> jax.Array:
    b = jax.tree_util.tree_leaves(x)[0].shape[0]
    return jnp.zeros((b,), jnp.float32)


# ---------------------------------------------------------------------------
# Heterogeneous chain engine
# ---------------------------------------------------------------------------


def chain_backward(layers, params, y, gy, gld, cond, use_fused: bool):
    """Reverse pass over a layer chain from the *output* side.

    Returns ``(x, gx, gparams_list, gcond)`` — the reconstructed chain input,
    its cotangent, per-layer parameter cotangents and the accumulated
    conditioning cotangent.  With ``use_fused`` each layer's ``fused_bwd``
    hook is taken when present (one sub-network evaluation per layer);
    otherwise — and for layers without the hook — the generic
    invert-then-vjp step runs.  Shared by the ``grad_mode="coupled"`` /
    ``"invertible"`` chain VJP and by ``InvertibleChain.fused_bwd`` (so
    *nested* chains inside a coupled outer chain stay fused).
    """
    gld = gld.astype(jnp.float32)
    gparams: list[Any] = [None] * len(layers)
    gcond = None
    for k in range(len(layers) - 1, -1, -1):
        layer, p = layers[k], params[k]
        fused = getattr(layer, "fused_bwd", None) if use_fused else None
        if fused is not None:
            # fused reversible step: reconstruction and local VJP share
            # one evaluation of the layer's sub-networks (§Perf/H1)
            x, gx, gp, gc = fused(p, y, gy, gld, cond)
            x = _stop(x)
        else:
            # 1. reconstruct this layer's input from its output
            x = _stop(layer.inverse(p, y, cond))
            # 2. differentiate the *single* layer locally (ordinary AD inside)
            y2, vjp = jax.vjp(
                lambda p_, x_, c_, _l=layer: _l.forward(p_, x_, c_), p, x, cond
            )
            gy = jax.tree_util.tree_map(lambda g, v: g.astype(v.dtype), gy, y2[0])
            gp, gx, gc = vjp((gy, gld.astype(y2[1].dtype)))
        gx = jax.tree_util.tree_map(lambda g, v: g.astype(v.dtype), gx, x)
        gparams[k] = gp
        gcond = _tree_add(gcond, gc)
        gy, y = gx, x
    return y, gy, gparams, gcond


def make_chain_apply(
    layers: Sequence[Invertible],
    grad_mode: str = "invertible",
    psum_axis: Optional[str] = None,
) -> Callable[..., tuple[PyTree, jax.Array]]:
    """Build ``apply(params_tuple, x, cond=None) -> (y, logdet)`` for a chain.

    ``params_tuple`` must have one entry per layer.  With
    ``grad_mode="invertible"`` the returned function carries a custom VJP whose
    residuals are only ``(params, output, cond)`` — intermediate activations
    are never stored.  ``grad_mode="coupled"`` keeps the same residuals but
    dispatches to each layer's ``fused_bwd`` hook when present (see module
    docstring), falling back to the generic invert-then-vjp step otherwise.

    ``psum_axis`` names a mapped mesh axis (``shard_map`` data parallelism):
    the custom VJP reduces ``gparams``/``gcond`` over it so the chain is
    SPMD-correct with batch-sharded ``x`` and replicated params (no effect
    on ``"autodiff"``, which has no custom VJP to reduce in).
    """
    layers = tuple(layers)

    def plain_apply(params, x, cond):
        logdet = _zero_logdet(x)
        for layer, p in zip(layers, params):
            x, ld = layer.forward(p, x, cond)
            logdet = logdet + ld.astype(logdet.dtype)
        return x, logdet

    if grad_mode == "autodiff":
        def plain(params, x, cond=None):
            return plain_apply(params, x, cond)

        return plain
    if grad_mode not in ("invertible", "coupled"):
        raise ValueError(
            f"chain engine supports invertible|coupled|autodiff, got {grad_mode}"
        )
    use_fused = grad_mode == "coupled"

    @jax.custom_vjp
    def apply(params, x, cond):
        return plain_apply(params, x, cond)

    def apply_fwd(params, x, cond):
        y, logdet = plain_apply(params, x, cond)
        # The memory win: residuals are the *output* (+ params/cond refs),
        # never the per-layer intermediates.
        return (y, logdet), (params, y, cond)

    def apply_bwd(res, cts):
        params, y, cond = res
        gy, gld = cts
        _x, gx, gparams, gcond = chain_backward(
            layers, params, y, gy, gld, cond, use_fused
        )
        # cond is per-example (batch-aligned with x) throughout the flow
        # zoo, so under shard_map it is sharded like x and its cotangent
        # stays per-shard — only the replicated params reduce
        gparams = [psum_cotangents(gp, psum_axis) for gp in gparams]
        return tuple(gparams), gx, gcond

    apply.defvjp(apply_fwd, apply_bwd)

    def wrapped(params, x, cond=None):
        return apply(tuple(params), x, cond)

    return wrapped


# ---------------------------------------------------------------------------
# Homogeneous scan engine (stacked params — production LM path)
# ---------------------------------------------------------------------------


def scan_backward(step_bwd, stacked, y, gy, gld, extra=None, unroll: int = 1):
    """Fused reversible reverse-scan from the *output* side.

    The scan-engine twin of :func:`chain_backward`: one ``lax.scan`` (reverse)
    whose body is the layer's fused ``step_bwd(p_i, y, gy, gld, extra, i) ->
    (x, gx, gparams_i, gextra_i)``.  Returns ``(x, gx, gstacked, gextra)`` —
    the reconstructed stack input, its cotangent, the layer-stacked parameter
    cotangents and the accumulated shared-pytree cotangent.  Shared by
    ``make_scan_apply(grad_mode="coupled")`` and by the scanned-GLOW
    ``GlowStepStack.fused_bwd`` hook (so a scanned stack nested inside a
    coupled chain keeps its megakernel backward AND its O(1)-in-depth HLO).
    """
    n = jax.tree_util.tree_leaves(stacked)[0].shape[0]
    ids = jnp.arange(n, dtype=jnp.int32)
    gld = gld.astype(jnp.float32)
    gextra0 = jax.tree_util.tree_map(lambda v: jnp.zeros(v.shape, v.dtype), extra)

    def body(carry, sp):
        yc, gyc, ge = carry
        p, i = sp
        # fused: one evaluation per unit reconstructs AND differentiates
        x, gx, gp, ge_i = step_bwd(p, yc, gyc, gld, extra, i)
        gx = jax.tree_util.tree_map(lambda g, v: g.astype(v.dtype), gx, x)
        return (x, gx, _tree_add(ge, ge_i)), gp

    (x0, gx, gextra), gstacked = lax.scan(
        body, (y, gy, gextra0), (stacked, ids), reverse=True, unroll=unroll
    )
    return x0, gx, gstacked, gextra


def make_scan_apply(
    step_fwd: Callable[[PyTree, PyTree, PyTree, jax.Array], tuple[PyTree, jax.Array]],
    step_inv: Callable[[PyTree, PyTree, PyTree, jax.Array], PyTree],
    grad_mode: str = "invertible",
    unroll: int = 1,
    step_bwd: Optional[Callable] = None,
    psum_axis: Optional[str] = None,
) -> Callable[..., tuple[PyTree, jax.Array]]:
    """Build ``apply(stacked_params, x, extra=None) -> (y, logdet)``.

    ``stacked_params`` leaves have a leading layer dimension ``L``;
    ``step_fwd(p_i, x, extra, i)`` applies layer ``i`` and returns
    ``(y, logdet_i)`` (``logdet_i`` shape ``(batch,)``; return zeros for
    measure-free layers such as LM blocks).  ``step_inv`` is its inverse.
    ``extra`` is a differentiable pytree shared across layers (shared
    attention params, conditioning, ...); its cotangent is accumulated in the
    backward carry, not stacked.  The carry structure/dtypes must be layer-
    independent (homogeneous stack).

    ``grad_mode="coupled"`` uses ``step_bwd(p, y, gy, gld, extra, i) ->
    (x, gx, gparams, gextra)`` — a *fused* reversible backward where the
    inverse reconstruction and the local VJP share one evaluation of each
    residual unit (RevNet-style; 4/3 fwd-equivalents instead of the generic
    engine's 5/3).  Beyond-paper optimization; see EXPERIMENTS.md §Perf/H1.

    ``psum_axis``: as in :func:`make_chain_apply` — the custom VJP reduces
    the stacked parameter cotangents (one collective on the whole stacked
    tree, after the reverse scan's per-shard accumulation) and the shared
    ``extra`` cotangent over the named mapped axis.
    """
    if grad_mode == "coupled" and step_bwd is None:
        raise ValueError("grad_mode='coupled' requires step_bwd")
    if grad_mode not in GRAD_MODES:
        raise ValueError(f"grad_mode must be one of {GRAD_MODES}, got {grad_mode}")

    def _layer_ids(stacked):
        n = jax.tree_util.tree_leaves(stacked)[0].shape[0]
        return jnp.arange(n, dtype=jnp.int32)

    def _forward_scan(stacked, x, extra, step):
        ids = _layer_ids(stacked)

        def body(carry, sp):
            xc, ld = carry
            p, i = sp
            y, ld_i = step(p, xc, extra, i)
            return (y, ld + ld_i.astype(ld.dtype)), None

        (y, ld), _ = lax.scan(body, (x, _zero_logdet(x)), (stacked, ids), unroll=unroll)
        return y, ld

    # -- baseline modes -----------------------------------------------------
    if grad_mode == "autodiff":
        def plain(stacked, x, extra=None):
            return _forward_scan(stacked, x, extra, step_fwd)

        return plain

    if grad_mode == "remat":
        ckpt_step = jax.checkpoint(step_fwd)

        def rematted(stacked, x, extra=None):
            return _forward_scan(stacked, x, extra, ckpt_step)

        return rematted

    # -- the paper's technique (+ the fused "coupled" variant) -----------------

    @jax.custom_vjp
    def apply(stacked, x, extra):
        return _forward_scan(stacked, x, extra, step_fwd)

    def apply_fwd(stacked, x, extra):
        y, ld = _forward_scan(stacked, x, extra, step_fwd)
        return (y, ld), (stacked, y, extra)

    def apply_bwd(res, cts):
        stacked, y, extra = res
        gy, gld = cts
        if grad_mode == "coupled":
            _x0, gx, gstacked, gextra = scan_backward(
                step_bwd, stacked, y, gy, gld, extra, unroll=unroll
            )
            return (
                psum_cotangents(gstacked, psum_axis),
                gx,
                psum_cotangents(gextra, psum_axis),
            )
        ids = _layer_ids(stacked)
        gld = gld.astype(jnp.float32)
        gextra0 = jax.tree_util.tree_map(lambda v: jnp.zeros(v.shape, v.dtype), extra)

        def body(carry, sp):
            yc, gyc, ge = carry
            p, i = sp
            # reconstruct the layer input from the layer output
            x = _stop(step_inv(p, yc, extra, i))
            y2, vjp = jax.vjp(
                lambda p_, x_, e_: step_fwd(p_, x_, e_, i), p, x, extra
            )
            gyc = jax.tree_util.tree_map(lambda g, v: g.astype(v.dtype), gyc, y2[0])
            gp, gx, ge_i = vjp((gyc, gld.astype(y2[1].dtype)))
            # keep the carry dtype stable across iterations
            gx = jax.tree_util.tree_map(lambda g, v: g.astype(v.dtype), gx, x)
            return (x, gx, _tree_add(ge, ge_i)), gp

        (x0, gx, gextra), gstacked = lax.scan(
            body, (y, gy, gextra0), (stacked, ids), reverse=True, unroll=unroll
        )
        return (
            psum_cotangents(gstacked, psum_axis),
            gx,
            psum_cotangents(gextra, psum_axis),
        )

    apply.defvjp(apply_fwd, apply_bwd)

    def wrapped(stacked, x, extra=None):
        return apply(stacked, x, extra)

    return wrapped


# ---------------------------------------------------------------------------
# Convenience: gradient through a flow NLL with either engine
# ---------------------------------------------------------------------------


def value_and_grad_nll(apply_fn, params, x, cond=None):
    """``(loss, grads)`` of the standard-normal NLL through ``apply_fn``.

    Works identically for invertible and autodiff modes — the invertible
    engine integrates with ``jax.grad`` transparently via its custom VJP,
    the JAX analogue of the package's ChainRules integration.
    """

    def loss_fn(p):
        z, logdet = apply_fn(p, x, cond)
        flat = jnp.concatenate(
            [jnp.reshape(v, (v.shape[0], -1)) for v in jax.tree_util.tree_leaves(z)],
            axis=1,
        )
        dim = flat.shape[1]
        logpz = -0.5 * jnp.sum(flat.astype(jnp.float32) ** 2, axis=1) - 0.5 * dim * jnp.log(
            2 * jnp.pi
        )
        return -jnp.mean(logpz + logdet) / dim

    # allow_int: invertible layers carry integer buffers (permutations,
    # signs); they receive float0 cotangents and are skipped by optimizers.
    return jax.value_and_grad(loss_fn, allow_int=True)(params)
