"""Seeded inputs: image batches and keys, made on the device from ``--seed``.

``images`` is the generator of ``repro.data.SyntheticImages`` (smooth
low-frequency images in [0, 1), dequantized), copied here so that the
benchmark's inputs do not move when the program's data module changes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def key_from_seed(seed: int) -> jax.Array:
    """A PRNG key that depends on every bit of a seed of up to 64 bits
    (``PRNGKey`` alone keeps only the low 32 bits)."""
    seed = int(seed)
    if not 0 <= seed < 2**64:
        raise ValueError(f"--seed must be in [0, 2**64), got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


def images(key, batch: int, size: int, channels: int = 3) -> jax.Array:
    k1, k2 = jax.random.split(key)
    coarse = jax.random.normal(k1, (batch, 4, 4, channels))
    img = jax.image.resize(coarse, (batch, size, size, channels), "bicubic")
    img = jax.nn.sigmoid(1.5 * img)
    deq = jax.random.uniform(k2, img.shape, minval=0.0, maxval=1.0 / 256)
    return (img * 255 / 256 + deq).astype(jnp.float32)


def image_pool(key, n: int, batch: int, size: int, sharding=None):
    """``n`` distinct batches in one jitted call, each placed with
    ``sharding`` (the layout the train step takes its batch in)."""
    def make(k):
        return tuple(images(kk, batch, size) for kk in jax.random.split(k, n))

    out_sh = None if sharding is None else (sharding,) * n
    return jax.jit(make, out_shardings=out_sh)(key)
