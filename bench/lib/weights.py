"""Weights of a GLOW configuration, made by the benchmark from the seed.

The benchmark makes the weights, not the program: ``make`` builds them in
the reference's layout (per scale, each leaf stacked over the ``K`` flow
steps), and ``to_program`` / ``from_program`` map that layout onto the
parameter tree of ``repro.core.build_glow_scanned`` and back.  The
initialisation follows GLOW (random rotations in LU form, He-scaled
conditioner convs) except that the last conditioner conv and the actnorm
are drawn small and non-zero, so that no coupling starts as the identity.
"""

from __future__ import annotations

import importlib
import math

import jax
import jax.numpy as jnp
from jax import lax

def build_flow(model: dict, psum_axis=None):
    """The program's flow for a configuration: its ``builder`` (a dotted
    name) called with the configuration's ``builder_args``."""
    module, name = model["builder"].rsplit(".", 1)
    builder = getattr(importlib.import_module(module), name)
    return builder(**{k: model[k] for k in model["builder_args"]}, psum_axis=psum_axis)


def check_layout(flow, params, example):
    """The benchmark's weights have the program's parameter layout."""
    want = jax.eval_shape(lambda x: flow.init(jax.random.PRNGKey(0), x), example)
    got = jax.tree_util.tree_map(lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype), params)
    if jax.tree_util.tree_structure(want) != jax.tree_util.tree_structure(got) or any(
        (a.shape, a.dtype) != (b.shape, b.dtype)
        for a, b in zip(jax.tree_util.tree_leaves(want), jax.tree_util.tree_leaves(got))
    ):
        raise RuntimeError("the program's parameter layout is not the one "
                           "bench/lib/weights.py builds")


#: float leaves of one scale, in the reference layout
FLOAT_LEAVES = ("an_log_s", "an_b", "lu_l", "lu_u", "lu_log_s",
                "w1", "b1", "w2", "b2", "w3", "b3")


def scale_shapes(model: dict, image_size: int):
    """``(spatial, channels)`` of each scale's flow steps: the squeeze
    quarters the positions and quadruples the channels, the split halves
    the channels."""
    out, hw, c = [], image_size, model["channels"]
    for s in range(model["n_scales"]):
        hw, c = hw // 2, c * 4
        out.append((hw, c))
        if s != model["n_scales"] - 1:
            c //= 2
    return out


def _lu_of_rotation(key, c):
    q, _ = jnp.linalg.qr(jax.random.normal(key, (c, c)))
    lu, _, perm = lax.linalg.lu(q)
    s = jnp.diagonal(lu)
    return {
        "lu_l": jnp.tril(lu, -1), "lu_u": jnp.triu(lu, 1),
        "lu_log_s": jnp.log(jnp.abs(s) + 1e-12),
        "inv_perm": jnp.argsort(perm).astype(jnp.int32),
        "sign_s": jnp.sign(s).astype(jnp.int8),
    }


def make(key, model: dict, image_size: int):
    """``(weights, buffers)``: per scale a dict of float leaves stacked over
    the flow steps, and a dict of the integer buffers (permutation, signs)."""
    k_steps, h = model["k_steps"], model["hidden"]
    init = model["init"]
    weights, buffers = [], []
    for s, (_, c) in enumerate(scale_shapes(model, image_size)):
        ks = jax.random.split(jax.random.fold_in(key, s), 6)
        ca = c // 2
        cin = c - ca
        lu = jax.vmap(lambda k: _lu_of_rotation(k, c))(jax.random.split(ks[0], k_steps))
        normal = jax.random.normal
        w = {
            "an_log_s": init["actnorm_std"] * normal(ks[1], (k_steps, c)),
            "an_b": init["actnorm_std"] * normal(ks[2], (k_steps, c)),
            "lu_l": lu["lu_l"], "lu_u": lu["lu_u"], "lu_log_s": lu["lu_log_s"],
            "w1": math.sqrt(2.0 / (9 * cin)) * normal(ks[3], (k_steps, 3, 3, cin, h)),
            "b1": jnp.zeros((k_steps, h)),
            "w2": math.sqrt(2.0 / h) * normal(ks[4], (k_steps, 1, 1, h, h)),
            "b2": jnp.zeros((k_steps, h)),
            "w3": init["last_conv_out_std"] / math.sqrt(9 * h)
            * normal(ks[5], (k_steps, 3, 3, h, c)),
            "b3": jnp.zeros((k_steps, c)),
        }
        weights.append({k: v.astype(jnp.float32) for k, v in w.items()})
        buffers.append({"inv_perm": lu["inv_perm"], "sign_s": lu["sign_s"]})
    return weights, buffers


def to_program(weights, buffers):
    """The parameter tuple of ``build_glow_scanned``: per scale a squeeze
    (no parameters), a flow-step stack, and a split except after the last."""
    layers = [{}]
    n = len(weights)
    for s, (w, b) in enumerate(zip(weights, buffers)):
        layers.append({})
        layers.append({
            "an": {"log_s": w["an_log_s"], "b": w["an_b"]},
            "lu": {"inv_perm": b["inv_perm"], "l": w["lu_l"], "u": w["lu_u"],
                   "sign_s": b["sign_s"], "log_s": w["lu_log_s"]},
            "net": {f"conv{i}": {"w": w[f"w{i}"], "b": w[f"b{i}"]} for i in (1, 2, 3)},
        })
        if s != n - 1:
            layers.append({})
    return tuple(layers)


def from_program(tree):
    """The float leaves of a program-layout tree (parameters, or an
    optimizer moment of the same structure) in the reference layout."""
    out = []
    for layer in tree:
        if isinstance(layer, dict) and "an" in layer:
            net = layer["net"]
            w = {"an_log_s": layer["an"]["log_s"], "an_b": layer["an"]["b"],
                 "lu_l": layer["lu"]["l"], "lu_u": layer["lu"]["u"],
                 "lu_log_s": layer["lu"]["log_s"]}
            for i in (1, 2, 3):
                w[f"w{i}"] = net[f"conv{i}"]["w"]
                w[f"b{i}"] = net[f"conv{i}"]["b"]
            out.append(w)
    return out


def step_norms(weights):
    """L2 norm of every leaf of every flow step: ``{"s<scale>.<leaf>":
    (K,) norms}``, the unit in which program and reference are compared."""
    out = {}
    for s, w in enumerate(weights):
        for name in FLOAT_LEAVES:
            v = w[name].astype(jnp.float32)
            out[f"s{s}.{name}"] = jnp.sqrt(jnp.sum(v * v, axis=tuple(range(1, v.ndim))))
    return out
