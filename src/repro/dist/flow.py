"""Data-parallel flow training and batch-sharded flow serving.

Two ways to scale a normalizing flow across a mesh's data axes:

* :func:`dp_value_and_grad_nll` — explicit SPMD via ``shard_map``: every
  device runs the memory-frugal reversible VJP on its batch shard, and the
  per-shard parameter cotangents (the fused kernels' ``gW`` / actnorm
  accumulators included) are reduced with ``lax.psum`` over the data axis
  *inside* the engine's custom VJP (``psum_axis`` — see
  :mod:`repro.core.autodiff`).  Gradients are bit-for-bit the single-device
  gradients up to reduction order (the conformance tests pin <= 1e-4).
* :func:`batch_parallel` — per-device ``sample`` / ``log_prob``: each
  device of the data axes runs the flow on its batch shard under
  ``shard_map`` — the path of ``serve.FlowServeEngine`` and (chunk by
  chunk) ``repro.uq.PosteriorEngine``'s streaming accumulation.
* :func:`shard_batch` — GSPMD placement: ``device_put`` a batch with its
  leading axis sharded and let ``jax.jit`` partition the graph — the
  amortized-posterior-sampling path of ``ConditionalFlow``.

Mesh-parity invariant the streaming-UQ layer builds on: latent noise is
always generated at full batch extent *before* :func:`shard_batch`
placement (see ``core.distributions.derive_key``), so the samples — and
any statistics accumulated over them — agree across mesh shapes to
compilation-level tolerance (pinned ≤ 1e-4 by ``tests/test_uq.py``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core.autodiff import psum_cotangents
from repro.dist.sharding import batch_sharding, data_axis_names, data_entry


def shard_batch(batch, mesh):
    """Place a batch pytree with its leading axis sharded over the mesh's
    data axes.  Leaves whose batch extent doesn't divide the data axes (and
    everything on a data-axis-free mesh) are left untouched."""
    if mesh is None or not data_axis_names(mesh):
        return batch
    n_data = math.prod(int(mesh.shape[a]) for a in data_axis_names(mesh))
    if n_data <= 1:
        return batch
    sharding = batch_sharding(mesh)

    def place(v):
        if v is None or not hasattr(v, "shape") or not v.shape:
            return v
        if v.shape[0] < n_data or v.shape[0] % n_data:
            return v
        return jax.device_put(v, sharding)

    return jax.tree_util.tree_map(place, batch)


def batch_parallel(fn, mesh):
    """Jit ``fn(params, *batch)`` so that every device of the mesh's data
    axes runs it on its own batch shard (``shard_map``; params replicate).

    For functions that are pointwise in the batch, such as a flow's
    ``inverse`` or ``log_prob``: no collective is needed, and each Pallas
    kernel runs per device, which GSPMD could not arrange (it cannot
    partition a TPU kernel).  A batch whose extent does not divide the data
    axes runs through the plain jit.
    """
    plain = jax.jit(fn)
    if mesh is None or not data_axis_names(mesh):
        return plain
    n_data = math.prod(int(mesh.shape[a]) for a in data_axis_names(mesh))
    if n_data <= 1:
        return plain
    axis = data_entry(mesh)
    mapped = jax.jit(
        lambda params, *batch: jax.shard_map(
            fn, mesh=mesh,
            in_specs=(P(),) + (P(axis),) * len(batch), out_specs=P(axis),
            check_vma=False,
        )(params, *batch)
    )

    def call(params, *batch):
        leaves = jax.tree_util.tree_leaves(batch)
        if any(not v.shape or v.shape[0] % n_data for v in leaves):
            return plain(params, *batch)
        return mapped(params, *batch)

    return call


def _nll(apply_fn, params, x, cond, scale: float):
    """Standard-normal NLL per dim (matches ``core.value_and_grad_nll``),
    scaled by ``scale`` so per-shard losses psum to the global mean."""
    z, logdet = apply_fn(params, x, cond)
    flat = jnp.concatenate(
        [jnp.reshape(v, (v.shape[0], -1)) for v in jax.tree_util.tree_leaves(z)],
        axis=1,
    )
    dim = flat.shape[1]
    logpz = -0.5 * jnp.sum(flat.astype(jnp.float32) ** 2, axis=1) - 0.5 * dim * jnp.log(
        2 * jnp.pi
    )
    return -jnp.mean(logpz + logdet) / dim * scale


def _densify_float0(grads, params):
    """Replace float0 cotangents (integer buffers: permutations, signs) with
    integer zeros so the gradient tree crosses the shard_map boundary."""

    def fix(g, p):
        if getattr(g, "dtype", None) == jax.dtypes.float0:
            return jnp.zeros(jnp.shape(p), jnp.asarray(p).dtype)
        return g

    return jax.tree_util.tree_map(fix, grads, params, is_leaf=lambda v: v is None)


def dp_value_and_grad_nll(flow, mesh, axis: str = "data", jit: bool = True):
    """Build ``vg(params, x, cond=None) -> (loss, grads)``: the data-parallel
    twin of :func:`repro.core.value_and_grad_nll`.

    ``x`` (and ``cond``, when given) are split over ``mesh[axis]``; params
    are replicated.  Each device differentiates its *local* mean NLL
    (pre-scaled by ``1/n_shards``) through the flow's reversible VJP.  When
    the flow was built with a matching ``psum_axis`` the engine reduces the
    parameter cotangents inside its custom VJP; otherwise (plain-AD flows,
    or the CPU "stored" coupled strategy, which differentiates by XLA's
    transpose) the reduction happens here.  Either way the returned loss and
    grads equal the single-device values up to f32 reduction order.
    """
    n_shards = int(mesh.shape[axis])
    vjp_reduces = getattr(flow, "psum_axis", None) == axis

    def per_device(params, x, cond):
        loss, grads = jax.value_and_grad(
            lambda p: _nll(flow.forward, p, x, cond, 1.0 / n_shards),
            allow_int=True,
        )(params)
        if not vjp_reduces:
            # plain-AD and CPU "stored" strategy flows land here; the
            # float0/None-aware reduction rule is shared with the engine VJPs
            grads = psum_cotangents(grads, axis)
        grads = _densify_float0(grads, params)
        return lax.psum(loss, axis), grads

    def vg(params, x, cond=None):
        fn = jax.shard_map(
            per_device,
            mesh=mesh,
            in_specs=(P(), P(axis), P(axis)),
            out_specs=(P(), P()),
            check_vma=False,
        )
        return fn(params, x, cond)

    return jax.jit(vg) if jit else vg
