"""Compile the flow hot path for a described TPU v5e, with no chip attached.

The TPU compiler is installed with JAX, and it compiles for a topology that
is only described.  That catches what interpret mode cannot: block shapes
that break the (8, 128) tiling rule, kernels too large for VMEM, programs
GSPMD cannot partition.  Nothing runs, so these tests say nothing about
results or times.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.  All such compiles stay in this one file.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.common import pick_block_m

# GLOW_FIG1 (3 scales, RGB, batch 8) at 64x64: each scale's post-squeeze
# (M, C) of the flow steps
B = 8
SCALES = [(1024, 12), (256, 24), (64, 48)]
KERNELS = [
    "flowstep_fwd", "flowstep_inv", "coupling_half_bwd", "spine_bwd",
    "coupling_fwd", "coupling_bwd", "coupling_inv",
    "conv1x1_mm", "conv1x1_gw",
]
#: the channel-major flow kernels, which the scanned GLOW runs
FLOW_KERNELS = ["flowstep_fwd", "flowstep_inv", "coupling_half_bwd", "spine_bwd"]
# GLOW_FIG1 at 256x256, the benchmark's size: (M, C) per scale
FIG1_256 = [(16384, 12), (4096, 24), (1024, 48)]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _kernel_call(name, m, c, sharding):
    """(jitted kernel, argument shapes) for one kernel at batch B, spatial
    m and c channels: (B, c, m) for the flow-step kernels, (B, m, c) for
    the rest."""
    from repro.kernels.conv1x1.conv1x1 import conv1x1_gw, conv1x1_mm
    from repro.kernels.coupling.coupling import coupling_bwd, coupling_fwd, coupling_inv
    from repro.kernels.flowstep.flowstep import (
        coupling_half_bwd,
        flowstep_fwd,
        flowstep_inv,
        spine_bwd,
    )

    def s(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)

    ca = c // 2
    full, half, vec, mat = s(B, m, c), s(B, m, ca), s(c), s(c, c)
    cm, cm_half = s(B, c, m), s(B, c - ca, m)
    return {
        "flowstep_fwd": (flowstep_fwd, (cm, vec, vec, mat, cm)),
        "flowstep_inv": (flowstep_inv, (cm, vec, vec, mat, cm)),
        "coupling_half_bwd": (coupling_half_bwd, (cm, cm, cm, s(B))),
        "spine_bwd": (spine_bwd, (cm, cm, cm_half, mat, mat, vec, vec)),
        "coupling_fwd": (coupling_fwd, (half, half, half)),
        "coupling_bwd": (coupling_bwd, (half, half, half, half, s(B))),
        "coupling_inv": (coupling_inv, (half, half, half)),
        "conv1x1_mm": (conv1x1_mm, (full, mat)),
        "conv1x1_gw": (conv1x1_gw, (full, full)),
    }[name]


def _compile_text(fn, args, **kw):
    return fn.lower(*args, **kw).compile().as_text()


@pytest.mark.parametrize("m,c", SCALES)
@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_v5e(one_chip, name, m, c):
    fn, args = _kernel_call(name, m, c, one_chip)
    hlo = _compile_text(fn, args, block_m=pick_block_m(m), interpret=False)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("m,c", FIG1_256)
@pytest.mark.parametrize("name", FLOW_KERNELS)
def test_flow_kernel_compiles_for_v5e_at_fig1_256(one_chip, name, m, c):
    """The scanned GLOW's kernels at the benchmark's shapes, with the block
    the step picks (several lane blocks per batch element at scale 1)."""
    fn, args = _kernel_call(name, m, c, one_chip)
    hlo = _compile_text(fn, args, interpret=False)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("name", FLOW_KERNELS)
def test_flow_kernel_compiles_for_v5e_at_large_ragged_m(one_chip, name):
    """A 1000x1000 image at scale 1: M = 250000, which no multiple of 128
    divides, is zero-padded into lane blocks that fit VMEM."""
    fn, args = _kernel_call(name, 250000, 12, one_chip)
    assert "tpu_custom_call" in _compile_text(fn, args, interpret=False)


@pytest.mark.parametrize("m", [300, 576, 1200])
def test_ragged_block_m_is_tile_aligned_and_compiles(one_chip, m):
    bm = pick_block_m(m)
    assert m % bm == 0 and (bm % 8 == 0 or bm == m), bm
    for name in ("flowstep_fwd", "spine_bwd", "coupling_bwd", "coupling_half_bwd"):
        fn, args = _kernel_call(name, m, 12, one_chip)
        assert "tpu_custom_call" in _compile_text(
            fn, args, block_m=bm, interpret=False
        )


def test_scanned_glow_train_step_compiles_for_v5e(one_chip, monkeypatch):
    """The whole jitted GLOW_SCANNED train step (reversible backward) at a
    small image: the kernels inside the scans and custom VJPs lower too."""
    from repro.config import TrainConfig
    from repro.configs.flows import GLOW_SCANNED, build_flow
    from repro.core.objectives import nll_loss
    from repro.optim import adamw_init
    from repro.train.loop import _make_step

    # steer the backend-resolved choices to what the chip would pick
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    monkeypatch.setenv("REPRO_COUPLED_BWD", "reversible")
    monkeypatch.setenv("REPRO_SCAN_UNROLL", "1")
    flow = build_flow(GLOW_SCANNED)
    x = jax.ShapeDtypeStruct((B, 32, 32, 3), jnp.float32)
    params = jax.eval_shape(lambda: flow.init(jax.random.PRNGKey(0), jnp.zeros(x.shape)))
    state = {"params": params, "opt": jax.eval_shape(adamw_init, params),
             "err": jax.tree_util.tree_map(lambda _: None, params)}
    step = _make_step(lambda p, b: nll_loss(flow, p, b), TrainConfig(steps=2))

    def place(tree):
        return jax.tree_util.tree_map(
            lambda v: None if v is None
            else jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one_chip),
            tree, is_leaf=lambda v: v is None,
        )

    hlo = _compile_text(step, (place(state), place(x), place(
        jax.ShapeDtypeStruct((), jnp.int32))))
    assert hlo.count("tpu_custom_call") >= 3  # fwd kernel + two bwd kernels
    for kernel in ("flowstep_fwd", "coupling_half_bwd", "spine_bwd"):
        assert kernel in hlo, kernel
