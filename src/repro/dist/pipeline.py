"""Microbatched pipeline parallelism over layer-stacked stage parameters.

GPipe-style schedule on a 1-D ``("pipe",)`` mesh axis via ``shard_map``:
stage parameters are stacked along a leading stage axis ``S`` and sharded so
each device holds exactly one stage; microbatches stream through the
pipeline with a ``lax.ppermute`` hand-off per tick.  With ``M`` microbatches
the schedule runs ``M + S - 1`` ticks — the classic bubble — and every
device executes the *same* program (the stage body), so the HLO is O(1) in
pipeline depth just like the scan-compiled stacks.

The forward is numerically identical to running all ``S * L_per`` blocks
sequentially on one device (the contract ``tests/test_distributed.py``
pins).  The tick loop is a ``lax.scan`` (not ``fori_loop``), so the whole
schedule is reverse-mode differentiable — the train loop's opt-in pipeline
mode (``repro.train.loop.train_pipeline``) backpropagates straight through
it, with the backward ``ppermute`` flowing upstream as the transpose of the
forward hand-off.  Reversible stage bodies additionally reconstruct their
inputs locally, so only the inter-stage boundary activations (and their
cotangents) ever cross devices.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def pipeline_stage_fn(block_apply: Callable, n_layers: int) -> Callable:
    """Lift a single-block ``block_apply(params_i, h) -> h`` into a stage
    function over ``n_layers`` layer-stacked parameters ``(n_layers, ...)``
    (one ``lax.scan`` — the stage body stays O(1) HLO in its depth)."""

    def stage(stage_params, h):
        def body(hc, p):
            return block_apply(p, hc), None

        h, _ = lax.scan(body, h, stage_params)
        return h

    return stage


def pipeline_forward(
    stage_fn: Callable,
    stage_params,
    x: jax.Array,
    mesh,
    axis: str = "pipe",
) -> jax.Array:
    """Run ``x`` through ``S`` pipeline stages sharded over ``mesh[axis]``.

    ``stage_params``: pytree whose leaves carry a leading stage axis ``S``
    (= the mesh axis size); each device holds its own stage slice.
    ``x``: ``(M, microbatch, ...)`` — ``M`` microbatches streamed through
    the pipeline.  Returns the ``(M, microbatch, ...)`` outputs after all
    stages, replicated across the axis.
    """
    n_stages = int(mesh.shape[axis])
    n_micro = x.shape[0]
    n_ticks = n_micro + n_stages - 1
    downstream = [(i, i + 1) for i in range(n_stages - 1)]

    def device_fn(w, xs):
        # local stage slice: drop the sharded leading stage axis (extent 1)
        w_local = jax.tree_util.tree_map(lambda v: v[0], w)
        idx = lax.axis_index(axis)
        buf = jnp.zeros(xs.shape[1:], xs.dtype)  # microbatch arriving upstream
        outs = jnp.zeros_like(xs)

        def tick(carry, t):
            buf, outs = carry
            # stage `idx` works on microbatch m = t - idx this tick
            m = t - idx
            m_clamped = jnp.clip(m, 0, n_micro - 1)
            x_in = lax.dynamic_index_in_dim(xs, m_clamped, 0, keepdims=False)
            h = jnp.where(idx == 0, x_in, buf)
            y = stage_fn(w_local, h)
            valid = (m >= 0) & (m < n_micro)
            # the last stage retires its finished microbatch into the output
            cur = lax.dynamic_index_in_dim(outs, m_clamped, 0, keepdims=False)
            outs = lax.dynamic_update_index_in_dim(
                outs,
                jnp.where(valid & (idx == n_stages - 1), y, cur),
                m_clamped,
                0,
            )
            # hand the activation to the next stage (device S-1 sends nowhere,
            # device 0 receives zeros — both ends idle into the bubble)
            buf = lax.ppermute(y, axis, downstream)
            return (buf, outs), None

        # scan (not fori_loop) keeps the schedule reverse-mode differentiable
        (_, outs), _ = lax.scan(tick, (buf, outs), jnp.arange(n_ticks))
        # only the last stage holds real outputs; psum replicates them
        keep = (idx == n_stages - 1).astype(outs.dtype)
        return lax.psum(outs * keep, axis)

    return jax.shard_map(
        device_fn,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )(stage_params, x)
