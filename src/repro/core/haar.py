"""Haar wavelet multiscale transform [5] and plain squeeze.

The orthonormal 2x2 Haar transform maps (B, H, W, C) -> (B, H/2, W/2, 4C)
with |det| = 1 (logdet = 0); it is its own inverse on the 2x2 block basis.
Used as the invertible down-sampling in GLOW-style multiscale flows and
hyperbolic networks (channel change without losing information).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.types import Invertible


def _blocks(x):
    a = x[:, 0::2, 0::2, :]
    b = x[:, 0::2, 1::2, :]
    c = x[:, 1::2, 0::2, :]
    d = x[:, 1::2, 1::2, :]
    return a, b, c, d


def _unblocks(a, b, c, d):
    """Inverse of :func:`_blocks`: interleave the four (B, H/2, W/2, C)
    phases back into (B, H, W, C) by stack-and-reshape (no strided scatter,
    so a batch-sharded input keeps its sharding)."""
    bsz, h2, w2, ch = a.shape
    top = jnp.stack([a, b], axis=3).reshape(bsz, h2, 2 * w2, ch)
    bottom = jnp.stack([c, d], axis=3).reshape(bsz, h2, 2 * w2, ch)
    return jnp.stack([top, bottom], axis=2).reshape(bsz, 2 * h2, 2 * w2, ch)


class _OrthonormalSqueeze(Invertible):
    """Shared ``grad_mode="coupled"`` hook for the parameter-free squeezes.

    Both squeezes are linear maps ``y = A x`` with ``A`` orthogonal (Haar: the
    symmetric orthonormal 2x2 wavelet basis; plain squeeze: a permutation), so
    the transpose needed by the VJP *is* the inverse: ``gx = A^T gy =
    inverse(gy)``.  The fused hook therefore reconstructs and differentiates
    with two inverse applications and no conditioner at all.
    """

    def fused_bwd(self, params, y, gy, gld, cond=None):
        x = jax.lax.stop_gradient(self.inverse(params, y, cond))
        gx = self.inverse(params, gy.astype(y.dtype), cond)
        return x, gx, {}, None


class HaarSqueeze(_OrthonormalSqueeze):
    """Orthonormal Haar squeeze; involution on the block basis."""

    def init(self, rng, x):
        if x.shape[1] % 2 or x.shape[2] % 2:
            raise ValueError(f"HaarSqueeze needs even H, W; got {x.shape}")
        return {}

    def forward(self, params, x, cond=None):
        a, b, c, d = _blocks(x)
        ll = (a + b + c + d) * 0.5
        lh = (a - b + c - d) * 0.5
        hl = (a + b - c - d) * 0.5
        hh = (a - b - c + d) * 0.5
        y = jnp.concatenate([ll, lh, hl, hh], axis=-1)
        return y, jnp.zeros((x.shape[0],), jnp.float32)

    def inverse(self, params, y, cond=None):
        c4 = y.shape[-1]
        assert c4 % 4 == 0
        c = c4 // 4
        ll, lh, hl, hh = (y[..., i * c : (i + 1) * c] for i in range(4))
        a = (ll + lh + hl + hh) * 0.5
        b = (ll - lh + hl - hh) * 0.5
        cc = (ll + lh - hl - hh) * 0.5
        d = (ll - lh - hl + hh) * 0.5
        return _unblocks(a, b, cc, d)


class Squeeze(_OrthonormalSqueeze):
    """Plain space-to-depth squeeze (RealNVP); logdet = 0."""

    def init(self, rng, x):
        if x.shape[1] % 2 or x.shape[2] % 2:
            raise ValueError(f"Squeeze needs even H, W; got {x.shape}")
        return {}

    def forward(self, params, x, cond=None):
        a, b, c, d = _blocks(x)
        y = jnp.concatenate([a, b, c, d], axis=-1)
        return y, jnp.zeros((x.shape[0],), jnp.float32)

    def inverse(self, params, y, cond=None):
        c4 = y.shape[-1]
        c = c4 // 4
        return _unblocks(*(y[..., i * c : (i + 1) * c] for i in range(4)))
