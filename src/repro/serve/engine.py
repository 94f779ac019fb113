"""Batched serving engines: LM prefill + jitted decode loop, and
batch-sharded flow sampling.

``ServeEngine`` serves a fixed LM decode batch (the assignment's
``decode_*`` shapes): one prefill over the prompt populates the caches,
then greedy/temperature decode steps append tokens.  The decode step is a
single jitted function of (params, caches, tokens, pos) — the function the
dry-run lowers for the decode cells.  With a ``mesh`` the params are
model-sharded and the caches batch-sharded by the ``repro.dist`` rules
before serving starts.

``FlowServeEngine`` serves a normalizing flow: jitted ``sample`` /
``log_prob`` whose batch axis is sharded over the mesh's data axes — the
amortized-posterior-sampling scale-out path (paper §4: thousands of
posterior draws per observation are embarrassingly batch-parallel).
"""

from __future__ import annotations

import itertools
from typing import Optional

import jax
import jax.numpy as jnp


class ServeEngine:
    def __init__(self, model, params, max_len: int, temperature: float = 0.0,
                 mesh=None):
        self.model = model
        self.mesh = mesh
        if mesh is not None:
            from repro.dist.sharding import params_pspecs, to_shardings

            params = jax.device_put(
                params, to_shardings(params_pspecs(params, mesh), mesh)
            )
        self.params = params
        self.max_len = max_len
        self.temperature = temperature
        self._decode = jax.jit(
            lambda p, tok, caches, pos, extra: model.decode_step(p, tok, caches, pos, extra)
        )
        self._prefill = jax.jit(lambda p, batch, caches: model.prefill(p, batch, caches))

    def _sample(self, logits, rng):
        if self.temperature == 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(rng, logits / self.temperature).astype(jnp.int32)

    def generate(
        self,
        batch: dict,
        max_new: int,
        rng: Optional[jax.Array] = None,
        eos_id: Optional[int] = None,
    ):
        """batch: prefill inputs (tokens + modality features).  Returns
        (generated tokens (B, max_new), per-step logits list)."""
        rng = jax.random.PRNGKey(0) if rng is None else rng
        bsz, prompt_len = batch["tokens"].shape
        caches = self.model.make_caches(bsz, self.max_len)
        if self.mesh is not None:
            from repro.dist.flow import shard_batch
            from repro.dist.sharding import cache_pspecs, to_shardings

            caches = jax.device_put(
                caches, to_shardings(cache_pspecs(caches, self.mesh), self.mesh)
            )
            batch = shard_batch(batch, self.mesh)
        logits, caches = self._prefill(self.params, batch, caches)

        extra = None
        cfg = self.model.cfg
        if cfg.is_enc_dec:
            # cache the encoder pass once; reuse for every decode step
            from repro.models.frontends import frontend_apply
            from repro.nn.norm import rmsnorm

            h = frontend_apply(self.params["frontend"], batch["frames"], cfg)
            enc, _ = self.model._stack_nocache(
                self.model.enc_layout.main, self.params["encoder"], h, None,
                h.shape[1], "autodiff",
            )
            extra = {"enc": rmsnorm(enc, self.params["enc_norm"], cfg.norm_eps)}

        n_prefix = (
            cfg.frontend.n_patches
            if (cfg.frontend is not None and cfg.frontend.kind == "vision")
            else 0
        )
        pos = prompt_len + n_prefix
        out_tokens = []
        done = jnp.zeros((bsz,), bool)
        tok = None
        for i in range(max_new):
            rng, krng = jax.random.split(rng)
            tok = self._sample(logits, krng)
            if eos_id is not None:
                done = done | (tok == eos_id)
                tok = jnp.where(done, eos_id, tok)
            out_tokens.append(tok)
            if bool(jnp.all(done)):
                break
            logits, caches = self._decode(
                self.params, tok[:, None], caches, jnp.asarray(pos + i, jnp.int32), extra
            )
        return jnp.stack(out_tokens, axis=1), logits


class FlowServeEngine:
    """Batch-sharded flow serving: ``sample`` / ``log_prob`` jitted once,
    with every batch split over the mesh's data axes and each device running
    the flow on its own shard (``repro.dist.flow.batch_parallel``; no
    collectives are needed — flows are pointwise in the batch).

    ``sample_flow``: optional inverse-optimized twin sharing ``flow``'s
    parameters (e.g. a ``kernel_inverse=True`` build) — the same contract
    as ``ConditionalFlow.sample_flow``.  Without a mesh this is just a
    jit-caching convenience wrapper, so callers can be mesh-agnostic.

    Each call records host spans on the profiler's clock, ``repro.serve.latents``
    (``sample`` only), ``repro.serve.place`` and ``repro.serve.dispatch``, all
    with ``request=<n>`` from one counter of the engine's calls.
    """

    def __init__(self, flow, params, mesh=None, sample_flow=None):
        self.flow = flow
        self.sample_flow = sample_flow if sample_flow is not None else flow
        if mesh is not None:  # replicated on every device of the mesh
            from jax.sharding import NamedSharding, PartitionSpec

            params = jax.device_put(params, NamedSharding(mesh, PartitionSpec()))
        self.params = params
        self.mesh = mesh
        self._calls = itertools.count()
        from repro.dist.flow import batch_parallel

        self._log_prob = batch_parallel(self._log_prob_impl, mesh)
        self._sample = batch_parallel(
            lambda p, z, cond: self.sample_flow.inverse(p, z, cond), mesh
        )

    def _log_prob_impl(self, params, x, cond):
        from repro.core.distributions import std_normal_logpdf

        z, logdet = self.flow.forward(params, x, cond)
        return std_normal_logpdf(z) + logdet

    def _place(self, *arrays):
        from repro.dist.flow import shard_batch

        return tuple(shard_batch(a, self.mesh) for a in arrays)

    def _span(self, name: str, request: int):
        return jax.profiler.TraceAnnotation(f"repro.serve.{name}", request=request)

    def log_prob(self, x, cond=None) -> jax.Array:
        """Per-example log density, batch-sharded over the data axes."""
        request = next(self._calls)
        with self._span("place", request):
            x, cond = self._place(x, cond)
        with self._span("dispatch", request):
            return self._log_prob(self.params, x, cond)

    # split-and-fold stream tag (`repro.core.distributions.derive_key`);
    # matches ConditionalFlow._TAG_SAMPLE so the two engines' draws from the
    # same user key describe the same latent stream
    _TAG_SAMPLE = 0

    def sample(self, rng, like, cond=None):
        """Draws shaped like the batched latent prototype ``like`` (an array
        or the tuple state of a multiscale flow — e.g. the ``z`` of a
        forward pass, or its ``jax.eval_shape``), batch-sharded over the
        data axes.  ``cond`` must already carry the same batch extent
        (repeat it per draw for amortized posterior batches —
        ``ConditionalFlow.sample`` does).

        The latent key is derived split-and-fold (``derive_key``): the same
        ``rng`` is bit-reproducible across calls and mesh shapes."""
        from repro.core.distributions import derive_key, std_normal_sample

        request = next(self._calls)
        with self._span("latents", request):
            z = std_normal_sample(derive_key(rng, self._TAG_SAMPLE), like)
        with self._span("place", request):
            z, cond = self._place(z, cond)
        with self._span("dispatch", request):
            return self._sample(self.params, z, cond)
