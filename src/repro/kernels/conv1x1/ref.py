"""Pure-jnp oracle for the 1x1-conv channel matmul.

Matches the kernel's numerics contract: operands in the activation dtype,
f32 accumulation at full f32 precision.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def conv1x1_mm_ref(x, w):
    y = jax.lax.dot_general(
        x,
        w.astype(x.dtype),
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    return y.astype(x.dtype)


def conv1x1_gw_ref(x, gy):
    """Weight cotangent ``sum_{b,m} x[b,m,:]^T gy[b,m,:]`` -> (C, C) f32."""
    return jnp.einsum(
        "bmi,bmj->ij", x.astype(jnp.float32), gy.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
