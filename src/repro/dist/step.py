"""The data-parallel training step: explicit SPMD via ``shard_map``.

This is the "make sharding earn its keep" path.  The mesh-aware train loop
previously GSPMD-jitted the single-device step with sharded inputs and let
the partitioner insert the gradient all-reduce — which (a) re-partitioned
the scanned megakernel program with enough glue to make 8-shard training
*slower* than single-device (the committed ``dp_scaling`` table bottomed
at 0.51x), and (b) ran ``compress_grads`` *after* GSPMD had already
all-reduced full-precision gradients, silently voiding the compression
module's only-compressed-bytes-on-the-wire contract.

Here every data shard runs the same program the single-device step runs —
on its batch shard — and the cross-shard reduction is explicit and placed
where it belongs:

* **compression off** — the flow engines' ``psum_axis`` custom-VJP hook
  reduces parameter cotangents *inside* the backward pass (one psum per
  cotangent tree, interleaved with backward compute rather than a single
  trailing all-reduce: the comm/compute-overlap structure), with an
  explicit ``psum_cotangents`` fallback for plain-AD losses;
* **compression on** — per-shard error-feedback compression runs *before*
  any collective and only the compressed payload crosses the axis
  (:func:`repro.optim.compression.compressed_allreduce`); the compiled
  step contains no dense gradient all-reduce, which
  ``benchmarks/flow_training.py`` verifies by walking the HLO collectives.

Gradient accumulation (``cfg.accum_steps`` microbatches per shard, O(1)
memory via ``optim.accum``) and the replicated AdamW update run inside the
same mapped program; the whole step is jitted with the previous train
state donated, so params/moments update in place.
"""

from __future__ import annotations

import math
from typing import Callable

import jax
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.config import TrainConfig
from repro.core.autodiff import psum_cotangents
from repro.dist.flow import _densify_float0
from repro.dist.sharding import batch_pspecs, data_axis_names
from repro.optim import adamw_update, compressed_allreduce, cosine_warmup
from repro.optim.accum import accumulate_grads


def dp_axis(mesh):
    """The mesh's combined data-parallel axis name(s) for collectives:
    a single name, a tuple of names (multi-pod), or ``None`` when the mesh
    has no data axes."""
    names = data_axis_names(mesh)
    if not names:
        return None
    return names if len(names) > 1 else names[0]


def dp_size(mesh) -> int:
    return math.prod(int(mesh.shape[a]) for a in data_axis_names(mesh))


def is_pure_dp(mesh) -> bool:
    """True when every non-trivial mesh axis is a data axis — the regime
    where params replicate and the ``shard_map`` fast path applies."""
    if mesh is None:
        return False
    n_data = dp_size(mesh)
    return n_data > 1 and n_data == math.prod(
        int(s) for s in mesh.devices.shape
    )


def make_dp_train_step(
    loss_fn: Callable,
    cfg: TrainConfig,
    mesh,
    state,
    batch,
    *,
    grads_reduced_by_vjp: bool = False,
) -> Callable:
    """Build the jitted data-parallel ``(state, batch, step) -> (state,
    metrics)`` update for a pure-DP mesh.

    ``loss_fn(params, local_batch) -> loss | (loss, aux)`` must return the
    *mean* loss over whatever batch it is given — each shard evaluates it
    on its slice, pre-scaled by ``1/n_shards`` so the loss (and through it
    the gradients) psum to the global mean.  ``grads_reduced_by_vjp``
    declares that the loss's custom VJP already psums parameter cotangents
    over the data axis (flows built with a matching ``psum_axis`` — the
    overlapped-reduction path); it is ignored when compression is on,
    which needs the raw per-shard cotangents on the near side of the wire.

    ``state`` is the loop's ``{"params", "opt", "err"}`` tree; with
    compression the error-feedback leaves carry a leading ``n_shards``
    axis (``compression_init(params, n_shards)``) and stay sharded —
    residuals are per-worker state and never cross the wire.
    """
    axis = dp_axis(mesh)
    n_data = dp_size(mesh)
    if axis is None or n_data <= 1:
        raise ValueError("make_dp_train_step needs a mesh with data axes")
    compression = cfg.grad_compression
    if compression != "none" and grads_reduced_by_vjp:
        # the VJP's dense in-backward psum would put full-precision bytes
        # on the wire before compression ever ran — use per-shard grads
        grads_reduced_by_vjp = False

    n_micro = max(int(getattr(cfg, "accum_steps", 1)), 1)
    local_batch = None
    for leaf in jax.tree_util.tree_leaves(batch):
        shape = getattr(leaf, "shape", ())
        if shape and shape[0] >= n_data and shape[0] % n_data == 0:
            local_batch = shape[0] // n_data
            break
    if local_batch is not None and local_batch % n_micro:
        raise ValueError(
            f"accum_steps={n_micro} does not divide the per-shard batch "
            f"{local_batch}"
        )

    def per_device(state, batch, step):
        params, err = state["params"], state["err"]
        # error-feedback residuals arrive as this shard's (1, ...) slice
        err_local = jax.tree_util.tree_map(
            lambda e: None if e is None else e[0], err,
            is_leaf=lambda v: v is None,
        )

        def lf(p, b):
            out = loss_fn(p, b)
            loss, aux = out if isinstance(out, tuple) else (out, {})
            return loss / n_data, aux

        loss, aux, grads = accumulate_grads(lf, params, batch, n_micro)
        grads = _densify_float0(grads, params)

        if compression != "none":
            # EF-compress per shard, exchange compressed payloads only
            grads, err_local = compressed_allreduce(
                grads, err_local, compression, axis, cfg.compression_ratio
            )
        elif not grads_reduced_by_vjp:
            grads = psum_cotangents(grads, axis)

        loss = lax.psum(loss, axis)
        aux = jax.tree_util.tree_map(
            lambda v: lax.pmean(v, axis)
            if jax.numpy.issubdtype(jax.numpy.asarray(v).dtype, jax.numpy.inexact)
            else v,
            aux,
        )
        lr = cosine_warmup(step, cfg.lr, cfg.warmup_steps, cfg.steps)
        params, opt, om = adamw_update(params, grads, state["opt"], cfg, lr)
        new_err = jax.tree_util.tree_map(
            lambda e: None if e is None else e[None], err_local,
            is_leaf=lambda v: v is None,
        )
        metrics = {"loss": loss, "lr": lr, **om, **aux}
        return {"params": params, "opt": opt, "err": new_err}, metrics

    def rep(tree):
        return jax.tree_util.tree_map(lambda _: P(), tree)

    state_specs = {
        "params": rep(state["params"]),
        "opt": rep(state["opt"]),
        "err": jax.tree_util.tree_map(
            lambda e: None if e is None else P(axis), state["err"],
            is_leaf=lambda v: v is None,
        ),
    }
    batch_specs = batch_pspecs(batch, mesh)
    out_metrics_spec = P()

    def step_fn(state, batch, step):
        fn = jax.shard_map(
            per_device,
            mesh=mesh,
            in_specs=(state_specs, batch_specs, P()),
            out_specs=(state_specs, out_metrics_spec),
            check_vma=False,
        )
        return fn(state, batch, step)

    # donate the previous train state: params/moments/residuals update
    # in place instead of allocating a second copy of the model
    return jax.jit(step_fn, donate_argnums=(0,))
