"""Sharded-flow conformance: the coupled fast path must survive sharding.

Subprocess cases (8 forged CPU host devices, the ``test_distributed.py``
pattern) pin the multi-device contracts:

* ``glow_scanned`` sharded ``log_prob`` and data-parallel **coupled**
  gradients match the single-device values <= 1e-4 (every backward
  strategy: reversible megakernel scan, generic invertible, CPU stored).
* batch-sharded sampling (``FlowServeEngine`` / ``ConditionalFlow``)
  returns the same samples as the unsharded inverse.

In-process cases cover the pure sharding-rule layer: a hypothesis test that
``params_pspecs`` round-trips arbitrary nested pytrees, the auto mesh
factoring, optimizer-spec mirroring, the autotune cache-dir override and
the checkpoint mesh-metadata warning.
"""

import importlib.util
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.skipif(
    importlib.util.find_spec("repro.dist") is None,
    reason="repro.dist (sharding/pipeline subsystem) not present in this build",
)


def _run(code: str, devices: int = 8):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr[-4000:]}"
    return out.stdout


# ---------------------------------------------------------------------------
# multi-device parity (subprocess)
# ---------------------------------------------------------------------------


def test_sharded_glow_scanned_matches_single_device():
    """Data-parallel loss/grads and batch-sharded log_prob of the scanned
    GLOW equal the single-device values for every coupled backward
    strategy, and sharded sampling equals the plain inverse."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import build_glow_scanned, value_and_grad_nll
    from repro.dist.flow import dp_value_and_grad_nll, shard_batch
    from repro.serve import FlowServeEngine

    x = jax.random.normal(jax.random.PRNGKey(1), (8, 8, 8, 4))
    mesh = jax.make_mesh((8,), ("data",))

    for mode, kw in (
        ("coupled", dict(coupled_bwd="reversible")),  # fused megakernel scan
        ("coupled", dict(coupled_bwd="stored")),      # CPU stored-activation
        ("invertible", {}),                           # generic paper engine
    ):
        flow = build_glow_scanned(n_scales=2, k_steps=2, hidden=8,
                                  grad_mode=mode, psum_axis="data", **kw)
        params = flow.init(jax.random.PRNGKey(0), x)
        loss0, g0 = value_and_grad_nll(flow.forward, params, x)
        loss1, g1 = dp_value_and_grad_nll(flow, mesh, axis="data")(params, x)
        assert abs(float(loss0) - float(loss1)) <= 1e-5, (mode, kw)
        l0 = jax.tree_util.tree_leaves(g0)
        l1 = jax.tree_util.tree_leaves(g1)
        assert len(l0) == len(l1)
        for a, b in zip(l0, l1):
            if a.dtype == jax.dtypes.float0:
                continue
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=1e-4, atol=1e-4, err_msg=f"{mode} {kw}")
        print(mode, kw or "-", "grads ok")

    # batch-sharded log_prob parity (GSPMD placement path)
    flow = build_glow_scanned(n_scales=2, k_steps=2, hidden=8,
                              grad_mode="coupled", coupled_bwd="reversible")
    params = flow.init(jax.random.PRNGKey(0), x)
    z0, ld0 = flow.forward(params, x)
    z1, ld1 = jax.jit(flow.forward)(params, shard_batch(x, mesh))
    np.testing.assert_allclose(np.asarray(ld1), np.asarray(ld0),
                               rtol=1e-5, atol=1e-5)

    # batch-sharded log_prob + sampling parity through the serving engine
    from repro.core.distributions import (
        derive_key, std_normal_logpdf, std_normal_sample)
    engine = FlowServeEngine(flow, params, mesh=mesh)
    lp = engine.log_prob(x)
    np.testing.assert_allclose(np.asarray(lp),
                               np.asarray(std_normal_logpdf(z0) + ld0),
                               rtol=1e-4, atol=1e-4)
    samples = engine.sample(jax.random.PRNGKey(2), z0)
    # the engine derives its latent stream split-and-fold from the user key
    zs = std_normal_sample(derive_key(jax.random.PRNGKey(2), 0), z0)
    ref = flow.inverse(params, zs)
    for s, r in zip(jax.tree_util.tree_leaves(samples),
                    jax.tree_util.tree_leaves(ref)):
        np.testing.assert_allclose(np.asarray(s), np.asarray(r),
                                   rtol=2e-4, atol=2e-4)
    print("sharded log_prob + sampling ok")
    """)


def test_conditional_sampling_batch_sharded():
    """Amortized posterior sampling: ``ConditionalFlow`` with a mesh shards
    the n-repeated-cond wide batch and matches the unsharded samples."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import ConditionalFlow, SummaryMLP, build_chint

    theta = jax.random.normal(jax.random.PRNGKey(0), (16, 8))
    y = jax.random.normal(jax.random.PRNGKey(1), (16, 12))
    mesh = jax.make_mesh((8,), ("data",))

    flow = build_chint(depth=2, recursion=1, hidden=16)
    plain = ConditionalFlow(flow, SummaryMLP(d_out=8, hidden=16))
    params = plain.init(jax.random.PRNGKey(2), theta, y)
    sharded = ConditionalFlow(plain.flow, plain.summary, mesh=mesh)

    lp0 = plain.log_prob(params, theta, y)
    lp1 = sharded.log_prob(params, theta, y)
    np.testing.assert_allclose(np.asarray(lp1), np.asarray(lp0),
                               rtol=1e-5, atol=1e-5)

    # 4 posterior draws per observation -> a 64-wide sharded inverse batch
    s0 = plain.sample(params, jax.random.PRNGKey(3), y, n=4, theta_dim=8)
    s1 = sharded.sample(params, jax.random.PRNGKey(3), y, n=4, theta_dim=8)
    assert s1.shape == (64, 8)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s0),
                               rtol=2e-4, atol=2e-4)
    print("conditional sharded sampling ok")
    """)


def test_train_flow_on_mesh_runs_and_checkpoints(tmp_path):
    """The mesh-aware training loop: a few sharded flow steps, then an
    elastic restore onto a *different* mesh shape resumes cleanly (and only
    warns about the mesh change)."""
    _run(f"""
    import warnings
    import jax, numpy as np
    from repro.config import TrainConfig
    from repro.core import build_glow_scanned
    from repro.data import SyntheticImages
    from repro.launch.mesh import make_auto_mesh
    from repro.train import train_flow

    flow = build_glow_scanned(n_scales=2, k_steps=2, hidden=8,
                              grad_mode="coupled")
    data = SyntheticImages(size=8, batch=8, seed=0)
    x0 = data.batch_at(0)
    cfg = TrainConfig(steps=3, lr=1e-3, warmup_steps=1, checkpoint_every=2,
                      checkpoint_dir=r"{tmp_path}")
    mesh_a = make_auto_mesh((8, 1))
    res_a = train_flow(flow, data, cfg, x0, mesh=mesh_a)
    assert res_a.final_step == 2

    # elastic restart on a different factoring of the same 8 devices
    cfg_b = TrainConfig(steps=5, lr=1e-3, warmup_steps=1, checkpoint_every=2,
                        checkpoint_dir=r"{tmp_path}")
    mesh_b = make_auto_mesh((4, 2))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        res_b = train_flow(flow, data, cfg_b, x0, mesh=mesh_b)
    assert res_b.final_step == 4
    assert any("mesh" in str(w.message) for w in caught), (
        "expected a mesh-mismatch warning on elastic restore")
    assert all(np.isfinite(l) for l in res_a.losses + res_b.losses)
    print("mesh train + elastic resume ok", res_a.losses[-1], res_b.losses[-1])
    """)


# ---------------------------------------------------------------------------
# sharding-rule units (in-process; mesh adapts to however many devices exist)
# ---------------------------------------------------------------------------


def test_auto_mesh_factoring():
    from repro.launch.mesh import auto_mesh_shape

    assert auto_mesh_shape(1) == (1, 1)
    assert auto_mesh_shape(2) == (2, 1)
    assert auto_mesh_shape(4) == (2, 2)
    assert auto_mesh_shape(6) == (3, 2)
    assert auto_mesh_shape(8) == (4, 2)
    assert auto_mesh_shape(256) == (16, 16)
    for n in range(1, 40):
        d, m = auto_mesh_shape(n)
        assert d * m == n and d >= m


def test_flow_training_refuses_model_sharded_mesh_with_compiled_kernels(
        monkeypatch):
    """GSPMD cannot partition a TPU kernel: on the compiled path, flow
    training on a mesh with a model axis fails up front with a clear
    message; pure data-parallel meshes and the oracle path are fine."""
    from jax.sharding import AbstractMesh

    from repro.config import TrainConfig
    from repro.kernels import common
    from repro.train import train_flow
    from repro.train.loop import _check_flow_mesh

    square = AbstractMesh((2, 2), ("data", "model"))
    monkeypatch.setenv(common.INTERPRET_ENV, "0")  # the chip's compiled path
    with pytest.raises(ValueError, match="pure data-parallel mesh"):
        train_flow(None, None, TrainConfig(), None, mesh=square)
    _check_flow_mesh(AbstractMesh((4, 1), ("data", "model")))
    monkeypatch.delenv(common.INTERPRET_ENV)
    _check_flow_mesh(square)  # CPU: GSPMD partitions the jnp oracles


def test_tune_cache_dir_env(monkeypatch, tmp_path):
    from repro.kernels import common

    monkeypatch.delenv(common.AUTOTUNE_CACHE_ENV, raising=False)
    monkeypatch.setenv(common.TUNE_CACHE_DIR_ENV, str(tmp_path))
    assert common._cache_path() == os.path.join(str(tmp_path), "block_m.json")
    # the explicit full-path override wins over the directory override
    monkeypatch.setenv(common.AUTOTUNE_CACHE_ENV, str(tmp_path / "pin.json"))
    assert common._cache_path() == str(tmp_path / "pin.json")
    monkeypatch.delenv(common.AUTOTUNE_CACHE_ENV, raising=False)
    monkeypatch.delenv(common.TUNE_CACHE_DIR_ENV, raising=False)
    assert common._cache_path() == common._DEFAULT_CACHE


def test_opt_pspecs_mirror_params_and_skip_int_buffers():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.dist.sharding import opt_pspecs, params_pspecs
    from repro.launch.mesh import make_auto_mesh
    from repro.optim import adamw_init

    params = {
        "w": jnp.zeros((4, 8)),
        "perm": jnp.arange(4, dtype=jnp.int32),
        "nested": {"b": jnp.zeros((8,))},
    }
    mesh = make_auto_mesh()
    p_specs = params_pspecs(params, mesh)
    opt = jax.eval_shape(adamw_init, params)
    o_specs = opt_pspecs(opt, p_specs, mesh)
    assert o_specs["step"] == P()
    assert o_specs["mu"]["w"] == p_specs["w"]
    assert o_specs["nu"]["nested"]["b"] == p_specs["nested"]["b"]
    # integer buffers have no moments and must stay spec-free
    assert jax.tree_util.tree_structure(o_specs["mu"]) == \
        jax.tree_util.tree_structure(opt["mu"])


def test_checkpoint_records_mesh_and_warns_on_mismatch(tmp_path):
    import json
    import warnings

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.train import checkpoint as ckpt

    mesh_a = jax.make_mesh((1, 1), ("data", "model"))
    state = {"w": jax.device_put(jnp.ones((4, 4)), NamedSharding(mesh_a, P()))}
    path = ckpt.save(state, str(tmp_path), 3)
    with open(os.path.join(path, "manifest.json")) as f:
        meta = json.load(f)["mesh"]
    assert meta == {"shape": [1, 1], "axis_names": ["data", "model"]}

    mesh_b = jax.make_mesh((1,), ("data",))
    sh_b = {"w": NamedSharding(mesh_b, P())}
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        restored, step = ckpt.restore(
            {"w": jnp.ones((4, 4))}, str(tmp_path), shardings=sh_b
        )
    assert step == 3
    assert any("mesh" in str(w.message) for w in caught)
    # same mesh: silent
    sh_a = {"w": NamedSharding(mesh_a, P())}
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        ckpt.restore({"w": jnp.ones((4, 4))}, str(tmp_path), shardings=sh_a)
    assert not any("mesh" in str(w.message) for w in caught)


# ---------------------------------------------------------------------------
# hypothesis: params_pspecs round-trips arbitrary nested pytrees
# (guarded per-test — the subprocess cases above must run without hypothesis)
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - optional dev dependency
    HAVE_HYPOTHESIS = False


def _leaf_arrays():
    import numpy as np

    shapes = st.lists(st.integers(1, 12), min_size=0, max_size=4)
    dtypes = st.sampled_from(["float32", "int32", "bfloat16"])
    return st.builds(
        lambda shape, dtype, seed: (
            np.arange(int(np.prod(shape)) or 1, dtype="float32")
            .reshape(shape or ())
            .astype(dtype)
            + seed
        ),
        shapes, dtypes, st.integers(0, 7),
    )


def _pytrees():
    return st.recursive(
        _leaf_arrays(),
        lambda children: st.one_of(
            st.lists(children, min_size=1, max_size=3).map(tuple),
            st.dictionaries(
                st.sampled_from(["w", "b", "lu", "net", "an", "scale"]),
                children, min_size=1, max_size=3,
            ),
        ),
        max_leaves=8,
    )


def _check_pspecs_roundtrip(tree):
    """Structure-preserving, divisibility-legal, and value-round-trip safe
    through ``device_put`` on whatever mesh this host can build."""
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec

    from repro.dist.sharding import params_pspecs, to_shardings
    from repro.launch.mesh import make_auto_mesh

    mesh = make_auto_mesh()
    specs = params_pspecs(tree, mesh)
    # same tree structure, PartitionSpec at every leaf
    assert jax.tree_util.tree_structure(specs) == jax.tree_util.tree_structure(
        tree
    )
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    for leaf, spec in zip(
        jax.tree_util.tree_leaves(tree), jax.tree_util.tree_leaves(specs)
    ):
        assert isinstance(spec, PartitionSpec)
        assert len(spec) <= leaf.ndim
        for d, entry in enumerate(spec):
            if entry is None:
                continue
            names = entry if isinstance(entry, tuple) else (entry,)
            n = int(np.prod([sizes[a] for a in names]))
            assert leaf.shape[d] % n == 0, (leaf.shape, spec)
    # values survive placement with the inferred shardings
    placed = jax.device_put(tree, to_shardings(specs, mesh))
    for a, b in zip(
        jax.tree_util.tree_leaves(placed), jax.tree_util.tree_leaves(tree)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


if HAVE_HYPOTHESIS:

    @settings(max_examples=30, deadline=None)
    @given(tree=_pytrees())
    def test_params_pspecs_roundtrip_arbitrary_pytrees(tree):
        _check_pspecs_roundtrip(tree)

else:  # keep the case visible (and the file importable) without hypothesis

    @pytest.mark.skip(reason="hypothesis not installed")
    def test_params_pspecs_roundtrip_arbitrary_pytrees():
        pass
