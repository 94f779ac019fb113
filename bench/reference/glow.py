"""Plain GLOW in ``jax.numpy``: the reference the benchmark checks against.

Written from the GLOW equations (Kingma & Dhariwal 2018) with this
benchmark's departures (see the configuration files): per scale a squeeze,
``K`` flow steps (actnorm, LU-parameterised 1x1 conv, affine coupling with
a 3x3-1x1-3x3 conditioner and the scale ``clamp * tanh(raw / clamp)``), and
a split except after the last scale; standard-normal latents throughout.
It imports nothing of the program: no kernels, no custom VJP, no scan
engine.  Gradients are plain autodiff, AdamW and its schedule are written
out below, and every matmul and conv runs at ``Precision.HIGHEST`` in float32.

``dtype`` and ``precision`` make the same code the control: bfloat16 at the
default precision is the nearest precision below the float32 that the
configurations state.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def _blocks(x):
    return x[:, 0::2, 0::2], x[:, 0::2, 1::2], x[:, 1::2, 0::2], x[:, 1::2, 1::2]


def _unblocks(a, b, c, d):
    bsz, h2, w2, ch = a.shape
    out = jnp.zeros((bsz, 2 * h2, 2 * w2, ch), a.dtype)
    out = out.at[:, 0::2, 0::2].set(a).at[:, 0::2, 1::2].set(b)
    return out.at[:, 1::2, 0::2].set(c).at[:, 1::2, 1::2].set(d)


class Glow:
    def __init__(self, model: dict, dtype=jnp.float32, precision=HIGHEST):
        self.n_scales = model["n_scales"]
        self.haar = model["haar"]
        self.clamp = model["clamp"]
        self.dtype = dtype
        self.precision = precision

    # -- squeeze ------------------------------------------------------------
    def squeeze(self, x):
        a, b, c, d = _blocks(x)
        if self.haar:
            a, b, c, d = ((a + b + c + d) * 0.5, (a - b + c - d) * 0.5,
                          (a + b - c - d) * 0.5, (a - b - c + d) * 0.5)
        return jnp.concatenate([a, b, c, d], axis=-1)

    def unsqueeze(self, y):
        a, b, c, d = jnp.split(y, 4, axis=-1)
        if self.haar:  # the orthonormal Haar map is its own inverse
            a, b, c, d = ((a + b + c + d) * 0.5, (a - b + c - d) * 0.5,
                          (a + b - c - d) * 0.5, (a - b - c + d) * 0.5)
        return _unblocks(a, b, c, d)

    # -- one flow step ------------------------------------------------------
    def _conv(self, x, w, b):
        y = lax.conv_general_dilated(
            x, w.astype(x.dtype), (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=self.precision)
        return y + b.astype(x.dtype)

    def conditioner(self, p, x):
        h = jax.nn.relu(self._conv(x, p["w1"], p["b1"]))
        h = jax.nn.relu(self._conv(h, p["w2"], p["b2"]))
        return self._conv(h, p["w3"], p["b3"])

    def weight(self, p, buf):
        """W = P L U: rows of L @ U permuted by ``inv_perm``."""
        c = p["lu_l"].shape[-1]
        lower = jnp.tril(p["lu_l"], -1) + jnp.eye(c, dtype=p["lu_l"].dtype)
        diag = buf["sign_s"].astype(p["lu_log_s"].dtype) * jnp.exp(p["lu_log_s"])
        upper = jnp.triu(p["lu_u"], 1) + jnp.diag(diag)
        return jnp.matmul(lower, upper, precision=self.precision)[buf["inv_perm"]]

    def _coupling_scale(self, raw):
        return self.clamp * jnp.tanh(raw / self.clamp)

    def step_forward(self, p, buf, x):
        hw = x.shape[1] * x.shape[2]
        ca = x.shape[-1] // 2
        x = x * jnp.exp(p["an_log_s"]) + p["an_b"]
        x = jnp.matmul(x, self.weight(p, buf).astype(x.dtype), precision=self.precision)
        xa, xb = x[..., :ca], x[..., ca:]
        h = self.conditioner(p, xb)
        log_s = self._coupling_scale(h[..., :ca])
        ya = xa * jnp.exp(log_s) + h[..., ca:]
        ld = (hw * (jnp.sum(p["an_log_s"]) + jnp.sum(p["lu_log_s"]))
              + jnp.sum(log_s, axis=(1, 2, 3)))
        return jnp.concatenate([ya, xb], axis=-1), ld

    def step_inverse(self, p, buf, y):
        ca = y.shape[-1] // 2
        ya, yb = y[..., :ca], y[..., ca:]
        h = self.conditioner(p, yb)
        log_s = self._coupling_scale(h[..., :ca])
        xa = (ya - h[..., ca:]) * jnp.exp(-log_s)
        w_inv = jnp.linalg.inv(self.weight(p, buf).astype(jnp.float32))
        x = jnp.matmul(jnp.concatenate([xa, yb], axis=-1), w_inv.astype(y.dtype),
                       precision=self.precision)
        return (x - p["an_b"]) * jnp.exp(-p["an_log_s"])

    # -- the whole flow -----------------------------------------------------
    def cast(self, weights):
        return jax.tree_util.tree_map(lambda v: v.astype(self.dtype), weights)

    def forward(self, weights, buffers, x):
        """``(latents, logdet)``: latents ``(x_top, z_1, ..., z_{L-1})``."""
        x = x.astype(self.dtype)
        logdet = jnp.zeros((x.shape[0],), self.dtype)
        zs = []
        for s, (w, buf) in enumerate(zip(weights, buffers)):
            x = self.squeeze(x)

            def body(carry, pb):
                xc, ld = carry
                y, dld = self.step_forward(pb[0], pb[1], xc)
                return (y, ld + dld.astype(ld.dtype)), None

            (x, logdet), _ = lax.scan(body, (x, logdet), (w, buf))
            if s != self.n_scales - 1:
                c = x.shape[-1] // 2
                x, z = x[..., :c], x[..., c:]
                zs.append(z)
        return (x, *zs), logdet

    def inverse(self, weights, buffers, latents):
        x, zs = latents[0].astype(self.dtype), list(latents[1:])
        for s in reversed(range(self.n_scales)):
            if s != self.n_scales - 1:
                x = jnp.concatenate([x, zs.pop().astype(self.dtype)], axis=-1)

            def body(y, pb):
                return self.step_inverse(pb[0], pb[1], y), None

            x, _ = lax.scan(body, x, (weights[s], buffers[s]), reverse=True)
            x = self.unsqueeze(x)
        return x

    def nll(self, weights, buffers, x):
        """Negative log-likelihood per dimension, averaged over the batch."""
        latents, logdet = self.forward(weights, buffers, x)
        flat = jnp.concatenate([z.reshape(z.shape[0], -1) for z in latents], axis=1)
        d = flat.shape[1]
        logp = -0.5 * jnp.sum(flat * flat, axis=1) - 0.5 * d * math.log(2 * math.pi)
        return -jnp.mean(logp + logdet) / d


def learning_rate(step: int, recipe: dict) -> float:
    """Linear warm-up to ``lr``, then cosine decay to ``min_lr_frac * lr``
    over ``decay_steps``; the floor holds after that."""
    lr, warm = recipe["lr"], recipe["warmup_steps"]
    if step < warm:
        return lr * step / max(warm, 1)
    prog = min(max((step - warm) / max(recipe["decay_steps"] - warm, 1), 0.0), 1.0)
    frac = recipe["min_lr_frac"]
    return lr * (frac + (1 - frac) * 0.5 * (1 + math.cos(math.pi * prog)))


def adamw(weights, grads, mu, nu, step: int, recipe: dict):
    """One AdamW step with global-norm clipping (step counts from 1).
    Returns the new weights and moments and the clipped gradient."""
    leaves = jax.tree_util.tree_leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in leaves))
    clip = recipe["grad_clip"]
    scale = jnp.where((clip > 0) & (gnorm > clip), clip / (gnorm + 1e-9), 1.0)
    b1, b2, eps, wd = recipe["b1"], recipe["b2"], recipe["eps"], recipe["weight_decay"]
    lr = learning_rate(step - 1, recipe)
    c1, c2 = 1.0 - b1 ** step, 1.0 - b2 ** step
    g = jax.tree_util.tree_map(lambda v: v.astype(jnp.float32) * scale, grads)
    mu = jax.tree_util.tree_map(lambda m, v: b1 * m + (1 - b1) * v, mu, g)
    nu = jax.tree_util.tree_map(lambda n, v: b2 * n + (1 - b2) * v * v, nu, g)

    def upd(p, m, n):
        p32 = p.astype(jnp.float32)
        delta = (m / c1) / (jnp.sqrt(n / c2) + eps) + wd * p32
        return (p32 - lr * delta).astype(p.dtype)

    return jax.tree_util.tree_map(upd, weights, mu, nu), mu, nu, g
