"""The training loop: jitted step, checkpoint/restart, preemption handling,
straggler watchdog, gradient compression, async input, pipeline mode.

Front-ends over one supervised loop:
  * ``train_lm(model, ...)``       — LM training (the production path)
  * ``train_flow(flow, ...)``      — flow NLL training (the paper's native path)
  * ``train_conditional_flow(...)``— amortized posterior training (repro.uq)
  * ``train_pipeline(...)``        — opt-in GPipe depth parallelism

All take an optional ``mesh``.  On a **pure data-parallel** mesh the step
is the explicit ``shard_map`` program from :mod:`repro.dist.step`: every
shard runs the single-device step on its batch slice, gradient reduction
is either overlapped into the backward (the flow engines' ``psum_axis``
custom-VJP hook) or error-feedback **compressed before the wire**
(``cfg.grad_compression``), gradient accumulation (``cfg.accum_steps``)
runs per shard, and the previous train state is donated.  On meshes with a
model axis the step falls back to GSPMD jit with explicit in/out
shardings, exactly as before.

The host input pipeline is asynchronous by default (``cfg.prefetch``):
step ``N+1``'s batch is produced — and on a mesh already placed with its
data-parallel sharding — by a background thread while step ``N`` runs.
Because the data sources are pure functions of the step index, prefetching
preserves the determinism/restart contract below bit-for-bit.

Fault-tolerance contract (tested): the loop can be killed at any step and
restarted; it resumes from the latest checkpoint, and — because the data
pipeline is a pure function of the step index — reproduces the exact same
final state it would have reached uninterrupted.  With a mesh, restarting
on a *different* mesh shape (elastic scaling) re-lays-out the restored
state onto the new mesh.
"""

from __future__ import annotations

import signal
import warnings
from dataclasses import dataclass
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.config import TrainConfig
from repro.core.distributions import std_normal_logpdf
from repro.data.pipeline import Prefetcher
from repro.optim import (
    adamw_init,
    adamw_update,
    compress_grads,
    compression_init,
    cosine_warmup,
)
from repro.optim.accum import accumulate_grads
from repro.train import checkpoint as ckpt
from repro.train.fault import FailureInjector, StragglerWatchdog, run_with_restarts


@dataclass
class TrainResult:
    params: Any
    opt_state: Any
    final_step: int
    losses: list
    restarts: int = 0
    flagged_steps: tuple = ()
    #: the jitted ``(state, batch, step) -> (state, metrics)`` update the
    #: loop ran (lower it to inspect the compiled program)
    step_fn: Any = None


def _dp_fast_path(mesh, cfg: TrainConfig) -> bool:
    """True when the mesh runs the explicit shard_map DP step."""
    if mesh is None:
        return False
    from repro.dist.step import is_pure_dp

    if not is_pure_dp(mesh):
        if cfg.grad_compression != "none":
            raise ValueError(
                "grad_compression requires a pure data-parallel mesh: on a "
                "model-sharded mesh the GSPMD partitioner inserts the dense "
                "gradient all-reduce itself, and compressing after the fact "
                "would not put compressed bytes on the wire"
            )
        return False
    return True


def _err_shards(mesh, cfg: TrainConfig) -> int | None:
    """Leading shard-axis extent for error-feedback state (None = local)."""
    if cfg.grad_compression == "none":
        return None
    if mesh is not None and _dp_fast_path(mesh, cfg):
        from repro.dist.step import dp_size

        return dp_size(mesh)
    return None


def _init_err(params, mesh, cfg: TrainConfig):
    if cfg.grad_compression == "none":
        # no accumulators: keeps state/checkpoints free of dead zero trees
        return jax.tree_util.tree_map(lambda _: None, params)
    return compression_init(params, _err_shards(mesh, cfg))


def _state_shardings(state, mesh):
    """NamedSharding tree for a ``{"params", "opt", "err"}`` train state:
    params model-sharded by the shared ``repro.dist`` rules, moments
    mirroring them, error-feedback accumulators sharded over the data axes
    along their per-shard leading axis (``None`` where absent)."""
    from jax.sharding import PartitionSpec
    from repro.dist.sharding import (
        data_axis_names,
        data_entry,
        opt_pspecs,
        params_pspecs,
        to_shardings,
    )

    p_specs = params_pspecs(state["params"], mesh)
    o_specs = opt_pspecs(state["opt"], p_specs, mesh)
    has_data = bool(data_axis_names(mesh))
    err_specs = jax.tree_util.tree_map(
        lambda e: None
        if e is None
        else (PartitionSpec(data_entry(mesh)) if has_data else PartitionSpec()),
        state["err"],
        is_leaf=lambda v: v is None,
    )
    return to_shardings(
        {"params": p_specs, "opt": o_specs, "err": err_specs}, mesh
    )


def _make_step(loss_fn: Callable, cfg: TrainConfig, mesh=None, state=None,
               batch=None, vjp_psum_axis=None):
    """Build the jitted (state, batch, step) -> (state, metrics) update.

    Pure-DP meshes get the explicit shard_map step (compression on the
    wire, overlapped/accumulated gradients, donated state —
    :func:`repro.dist.step.make_dp_train_step`); model-sharded meshes keep
    the GSPMD jit with explicit in/out shardings; no mesh jits the plain
    single-device step.  ``vjp_psum_axis``: the loss's custom VJP already
    reduces parameter cotangents over that mesh axis (flow engines built
    with ``psum_axis``)."""
    if mesh is not None and _dp_fast_path(mesh, cfg):
        from repro.dist.step import dp_axis, make_dp_train_step

        if cfg.grad_compression != "none" and vjp_psum_axis is not None:
            raise ValueError(
                "grad_compression with a psum_axis flow: the engine VJP "
                "would all-reduce dense cotangents before compression — "
                "build the flow without psum_axis to train compressed"
            )
        return make_dp_train_step(
            loss_fn, cfg, mesh, state, batch,
            grads_reduced_by_vjp=(
                vjp_psum_axis is not None and vjp_psum_axis == dp_axis(mesh)
            ),
        )

    n_micro = max(int(cfg.accum_steps), 1)

    def step_fn(state, batch, step):
        def lf(p, b):
            out = loss_fn(p, b)
            return out if isinstance(out, tuple) else (out, {})

        loss, aux, grads = accumulate_grads(lf, state["params"], batch, n_micro)
        # local error-feedback compression (single-process: nothing crosses
        # a wire here; the distributed twin lives in repro.dist.step)
        grads, new_err = compress_grads(
            grads, state["err"], cfg.grad_compression, cfg.compression_ratio
        )
        with jax.named_scope("optimizer"):  # the global-norm clip and AdamW
            lr = cosine_warmup(step, cfg.lr, cfg.warmup_steps, cfg.steps)
            params, opt, om = adamw_update(state["params"], grads, state["opt"], cfg, lr)
        metrics = {"loss": loss, "lr": lr, **om, **aux}
        return {"params": params, "opt": opt, "err": new_err}, metrics

    if mesh is None:
        return jax.jit(step_fn, donate_argnums=(0,))

    from repro.dist.sharding import batch_pspecs, to_shardings

    state_sh = _state_shardings(state, mesh)
    batch_sh = to_shardings(batch_pspecs(batch, mesh), mesh)
    return jax.jit(
        step_fn,
        in_shardings=(state_sh, batch_sh, None),
        out_shardings=(state_sh, None),
        donate_argnums=(0,),
    )


def _check_flow_mesh(mesh):
    """Refuse a model-sharded mesh for a flow step that calls compiled
    Pallas kernels: GSPMD cannot partition a TPU kernel, so such a step
    would not compile.  Pure data-parallel meshes run the ``shard_map`` step,
    in which every device calls the kernels on its own shard."""
    if mesh is None:
        return
    from repro.dist.step import dp_size
    from repro.kernels.common import kernel_path

    if mesh.size > dp_size(mesh) and kernel_path() == "compiled":
        raise ValueError(
            f"flow training on mesh {dict(mesh.shape)} needs GSPMD to split "
            "the compiled Pallas kernels over its non-data axes, which it "
            "cannot do; use a pure data-parallel mesh instead (e.g. "
            f"--mesh {mesh.size},1)"
        )


def _span(name: str, step: int):
    """A host span ``repro.train.<name>`` of ``step`` on the profiler's clock,
    so a trace of the loop names what the host did between device ops (it
    costs a flag check when no trace is being taken)."""
    return jax.profiler.TraceAnnotation(f"repro.train.{name}", step_num=step)


def _restore_state(like, cfg: TrainConfig, shardings):
    """Checkpoint restore that survives error-feedback shape changes: an
    elastic restart onto a different data-parallel width re-zeros the
    per-shard residuals (an optimization detail, not model state) instead
    of failing."""
    try:
        return ckpt.restore(like, cfg.checkpoint_dir, shardings=shardings)
    except ValueError as e:
        if "['err']" not in str(e):
            raise
        sub = {"params": like["params"], "opt": like["opt"]}
        sub_sh = (
            {"params": shardings["params"], "opt": shardings["opt"]}
            if shardings is not None
            else None
        )
        state, step = ckpt.restore(sub, cfg.checkpoint_dir, shardings=sub_sh)
        warnings.warn(
            "error-feedback accumulator shape changed across restart "
            "(elastic data-parallel resize); residuals re-zeroed",
            stacklevel=2,
        )
        state["err"] = like["err"]
        return state, step


def _supervised_loop(
    loss_fn: Callable,
    init_params_fn: Callable[[], Any],
    data_fn: Callable[[int], Any],
    cfg: TrainConfig,
    *,
    mesh=None,
    injector: Optional[FailureInjector] = None,
    log_every: int = 0,
    vjp_psum_axis=None,
) -> TrainResult:
    # the jitted step is built lazily on the first batch of the first
    # attempt (mesh-aware jit needs state/batch prototypes); the cache
    # carries it across restarts
    step_cache: dict = {"fn": None}
    watchdog = (
        StragglerWatchdog(cfg.step_timeout_s) if cfg.step_timeout_s > 0 else None
    )
    restarts = {"n": 0}

    # cooperative preemption: checkpoint on SIGTERM, then exit cleanly
    preempted = {"flag": False}

    def _on_sigterm(signum, frame):  # pragma: no cover - signal path
        preempted["flag"] = True

    old_handler = None
    try:
        old_handler = signal.signal(signal.SIGTERM, _on_sigterm)
    except ValueError:  # non-main thread (tests)
        pass

    if mesh is not None:
        from repro.dist.flow import shard_batch

        def batch_fn(step: int):
            # placement happens here too, so the prefetch thread produces
            # *device-resident, correctly sharded* batches ahead of time
            return shard_batch(data_fn(step), mesh)
    else:
        batch_fn = data_fn

    def attempt_run(attempt: int) -> TrainResult:
        start = ckpt.latest_step(cfg.checkpoint_dir)
        if start is not None:
            like = {"params": init_params_fn(), "opt": None, "err": None}
            like["opt"] = adamw_init(like["params"])
            like["err"] = _init_err(like["params"], mesh, cfg)
            # elastic restart: arrays land directly in the *current* mesh's
            # layout, whatever mesh the checkpoint was written under
            shardings = _state_shardings(like, mesh) if mesh is not None else None
            with _span("restore", start):
                state, start_step = _restore_state(like, cfg, shardings)
            start_step += 1
        else:
            params = init_params_fn()
            state = {
                "params": params,
                "opt": adamw_init(params),
                "err": _init_err(params, mesh, cfg),
            }
            start_step = 0
        if mesh is not None:
            state = jax.device_put(state, _state_shardings(state, mesh))

        prefetch = (
            Prefetcher(batch_fn, start_step, lookahead=cfg.prefetch)
            if cfg.prefetch > 0
            else None
        )
        losses = []
        step = start_step
        saved_at = None
        try:
            for step in range(start_step, cfg.steps):
                with jax.profiler.StepTraceAnnotation("repro.train", step_num=step):
                    if watchdog is not None:
                        watchdog.start_step(step)
                    try:
                        if injector is not None:
                            injector.maybe_fail(step)
                        with _span("fetch", step):
                            if prefetch is not None:
                                got_step, batch = prefetch.get()
                            else:
                                got_step, batch = step, batch_fn(step)
                        if got_step != step:  # pragma: no cover - invariant
                            raise RuntimeError(
                                f"prefetch out of order: wanted {step}, "
                                f"got {got_step}"
                            )
                        if step_cache["fn"] is None:
                            with _span("build", step):
                                step_cache["fn"] = _make_step(
                                    loss_fn, cfg, mesh=mesh, state=state,
                                    batch=batch, vjp_psum_axis=vjp_psum_axis,
                                )
                        with _span("dispatch", step):
                            state, metrics = step_cache["fn"](
                                state, batch, jnp.asarray(step, jnp.int32)
                            )
                    finally:
                        # the deadline timer must die with the step — a step
                        # that *raises* would otherwise leave it running and
                        # flag the restarted attempt's re-run as a straggler
                        if watchdog is not None:
                            watchdog.end_step()
                    with _span("readback", step):
                        loss = float(metrics["loss"])
                    losses.append(loss)
                    if log_every and step % log_every == 0:
                        print(f"step {step:6d}  loss {loss:.4f}  "
                              f"lr {float(metrics['lr']):.2e}")
                    if (step + 1) % cfg.checkpoint_every == 0 or preempted["flag"]:
                        with _span("checkpoint", step):
                            ckpt.save(state, cfg.checkpoint_dir, step,
                                      cfg.keep_checkpoints)
                        saved_at = step
                        if preempted["flag"]:
                            break
            else:
                step = cfg.steps - 1
        finally:
            if prefetch is not None:
                prefetch.close()
        if saved_at != step:  # skip the redundant back-to-back final save
            with _span("checkpoint", step):
                ckpt.save(state, cfg.checkpoint_dir, step, cfg.keep_checkpoints)
        return TrainResult(
            params=state["params"],
            opt_state=state["opt"],
            final_step=step,
            losses=losses,
            restarts=restarts["n"],
            flagged_steps=tuple(watchdog.flagged_steps) if watchdog else (),
            step_fn=step_cache["fn"],
        )

    def on_restart(attempt, exc):
        restarts["n"] = attempt

    try:
        return run_with_restarts(
            attempt_run, max_restarts=cfg.max_restarts, on_restart=on_restart
        )
    finally:
        if old_handler is not None:
            signal.signal(signal.SIGTERM, old_handler)


# ---------------------------------------------------------------------------
# front-ends
# ---------------------------------------------------------------------------


def train_lm(model, data, cfg: TrainConfig, rng=None, grad_mode=None,
             mesh=None, injector=None, log_every: int = 0) -> TrainResult:
    rng = jax.random.PRNGKey(cfg.seed) if rng is None else rng

    def loss_fn(params, batch):
        return model.train_loss(params, batch, grad_mode=grad_mode)

    return _supervised_loop(
        loss_fn,
        lambda: model.init(rng),
        lambda step: data.batch_at(step),
        cfg,
        mesh=mesh,
        injector=injector,
        log_every=log_every,
    )


def train_conditional_flow(model, data, cfg: TrainConfig, rng=None, mesh=None,
                           injector=None, log_every: int = 0) -> TrainResult:
    """Amortized posterior training (``repro.uq``): ``model`` is a
    ``ConditionalFlow`` (its ``train_loss`` hook is the objective) and
    ``data.batch_at(step)`` yields ``{"theta", "y"}`` joint draws — e.g. an
    operator problem from ``repro.uq.operators``.  Full supervised-loop
    contract: checkpoints, restarts, mesh sharding."""
    _check_flow_mesh(mesh)
    rng = jax.random.PRNGKey(cfg.seed) if rng is None else rng
    b0 = data.batch_at(0)

    return _supervised_loop(
        lambda params, batch: model.train_loss(params, batch),
        lambda: model.init(rng, b0["theta"], b0["y"]),
        lambda step: data.batch_at(step),
        cfg,
        mesh=mesh,
        injector=injector,
        log_every=log_every,
    )


def train_flow(flow, data, cfg: TrainConfig, example, rng=None, cond_fn=None,
               mesh=None, injector=None, log_every: int = 0) -> TrainResult:
    """``data.batch_at(step)`` returns x (or a dict with 'theta'/'y' for
    conditional flows via ``cond_fn(batch) -> (x, cond)``).

    A flow built with ``psum_axis`` matching the mesh's data axis reduces
    its parameter cotangents *inside* the reversible backward — the DP step
    then skips its own reduction (the overlapped-collective path)."""
    _check_flow_mesh(mesh)
    rng = jax.random.PRNGKey(cfg.seed) if rng is None else rng

    def loss_fn(params, batch):
        if cond_fn is not None:
            x, cond = cond_fn(batch)
        else:
            x, cond = batch, None
        z, logdet = flow.forward(params, x, cond)
        from repro.core.distributions import flatten_state

        with jax.named_scope("loss"):
            d = flatten_state(z).shape[1]
            loss = -jnp.mean(std_normal_logpdf(z) + logdet) / d
        return loss, {}

    def init_fn():
        if isinstance(example, tuple):
            return flow.init(rng, example[0], cond=example[1])
        return flow.init(rng, example)

    return _supervised_loop(
        loss_fn,
        init_fn,
        lambda step: data.batch_at(step),
        cfg,
        mesh=mesh,
        injector=injector,
        log_every=log_every,
        vjp_psum_axis=getattr(flow, "psum_axis", None),
    )


def train_pipeline(block_apply, init_fn, data, cfg: TrainConfig, *, mesh,
                   loss_head, n_layers_per_stage: int, injector=None,
                   log_every: int = 0) -> TrainResult:
    """Opt-in GPipe depth parallelism (``repro.dist.pipeline``) under the
    full supervised-loop contract.

    ``init_fn()`` must return params with a ``"stages"`` entry whose leaves
    are stage-stacked ``(S, n_layers_per_stage, ...)`` for the mesh's
    ``cfg.pipeline_axis`` (extent ``S``); ``block_apply(p, h) -> h`` is a
    single block; ``loss_head(params, h, batch) -> scalar`` consumes the
    pipeline output.  Each step reshapes the batch into
    ``cfg.pipeline_microbatches`` microbatches, streams them through the
    stage devices with per-tick ``ppermute`` hand-offs, and differentiates
    straight through the schedule (the tick loop is a ``lax.scan``).
    """
    from repro.dist.pipeline import pipeline_forward, pipeline_stage_fn

    n_micro = cfg.pipeline_microbatches
    if n_micro <= 0:
        raise ValueError("train_pipeline needs cfg.pipeline_microbatches > 0")
    if mesh is None or cfg.pipeline_axis not in mesh.axis_names:
        raise ValueError(
            f"train_pipeline needs a mesh with a {cfg.pipeline_axis!r} axis"
        )
    stage = pipeline_stage_fn(block_apply, n_layers_per_stage)

    def loss_fn(params, batch):
        x = batch["x"]
        if x.shape[0] % n_micro:
            raise ValueError(
                f"pipeline_microbatches={n_micro} does not divide the "
                f"batch {x.shape[0]}"
            )
        xm = x.reshape((n_micro, x.shape[0] // n_micro) + x.shape[1:])
        h = pipeline_forward(
            stage, params["stages"], xm, mesh, axis=cfg.pipeline_axis
        )
        h = h.reshape((x.shape[0],) + h.shape[2:])
        return loss_head(params, h, batch)

    return _supervised_loop(
        loss_fn,
        init_fn,
        lambda step: data.batch_at(step),
        cfg,
        mesh=mesh,
        injector=injector,
        log_every=log_every,
    )
