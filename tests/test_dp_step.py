"""Data-parallel train-step conformance (8 forged CPU host devices).

The contracts behind ``repro.dist.step`` — the explicit ``shard_map`` DP
step the training loop runs on pure data-parallel meshes:

* the 8-shard step (with and without gradient accumulation, with the
  prefetched input pipeline) reproduces the single-device run exactly;
* error-feedback compressed collectives: int8 matches the dense reduction
  within quantization tolerance, top-k at ratio 1.0 matches it exactly,
  and the residual telescopes (sent + carried == gradient, per shard);
* the compiled compressed step carries strictly fewer collective bytes
  than the dense step and contains **no** dense-gradient all-reduce;
* a flow built with ``psum_axis`` (reduction overlapped into the custom
  VJP) yields the same updated params as the trailing explicit reduction;
* the opt-in GPipe mode (``train_pipeline``) backpropagates through the
  microbatched schedule and learns.
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


def test_dp_training_matches_single_device_with_accum_and_prefetch():
    """The whole mesh-aware loop (prefetched input, donated state, shard_map
    step) at 8 shards reproduces the single-device loop step-for-step, with
    and without per-shard gradient accumulation."""
    _run("""
    import jax, numpy as np, tempfile
    from jax.sharding import Mesh
    from repro.config import TrainConfig
    from repro.core import build_glow_scanned
    from repro.data import SyntheticImages
    from repro.train.loop import train_flow

    data = SyntheticImages(size=8, batch=16, seed=0)
    ex = data.batch_at(0)
    flow = build_glow_scanned(n_scales=2, k_steps=2, hidden=16,
                              grad_mode="coupled")

    def run(mesh, accum=1, prefetch=2):
        cfg = TrainConfig(steps=5, lr=1e-3, warmup_steps=2,
                          checkpoint_every=100,
                          checkpoint_dir=tempfile.mkdtemp(),
                          accum_steps=accum, prefetch=prefetch)
        return train_flow(flow, data, cfg, ex, mesh=mesh)

    ref = run(None, prefetch=0)
    mesh = Mesh(np.array(jax.devices()).reshape(8, 1), ("data", "model"))
    for accum in (1, 2):
        res = run(mesh, accum=accum)
        d = max(abs(a - b) for a, b in zip(ref.losses, res.losses))
        assert d < 1e-4, f"accum={accum}: loss divergence {d}"
        pd = jax.tree_util.tree_map(
            lambda a, b: float(jax.numpy.max(jax.numpy.abs(a - b))),
            ref.params, res.params)
        m = max(jax.tree_util.tree_leaves(pd))
        assert m < 1e-4, f"accum={accum}: param divergence {m}"
    print("dp loop parity ok")
    """)


def test_compressed_allreduce_parity_and_error_feedback():
    """shard_map-level contracts of ``compressed_allreduce``: top-k at
    ratio 1.0 equals the dense psum exactly; int8 is within quantization
    tolerance; per-shard residuals telescope (sent + carried == g + err)."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from jax import lax
    from jax.sharding import PartitionSpec as P
    from repro.optim import compressed_allreduce

    mesh = jax.make_mesh((8,), ("data",))
    k = jax.random.PRNGKey(0)
    g = jax.random.normal(k, (8, 6, 10))          # per-shard gradients
    err = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (8, 6, 10))
    dense = jnp.sum(g + err, axis=0)              # ideal EF-corrected sum

    def make(method, ratio):
        def f(gs, es):
            red, new_e = compressed_allreduce(
                {"w": gs[0]}, {"w": es[0]}, method, "data", ratio)
            return red["w"], new_e["w"][None]
        return jax.shard_map(f, mesh=mesh, in_specs=(P("data"), P("data")),
                         out_specs=(P(), P("data")), check_vma=False)

    # top-k, ratio 1.0: everything is sent -> exact dense sum, zero residual
    red, new_e = make("topk", 1.0)(g, err)
    np.testing.assert_allclose(np.asarray(red), np.asarray(dense),
                               rtol=1e-5, atol=1e-5)
    assert float(jnp.max(jnp.abs(new_e))) == 0.0

    # int8: within per-leaf quantization tolerance of the dense sum
    red8, new_e8 = make("int8", 0.0)(g, err)
    scale = float(jnp.max(jnp.abs(g + err))) / 127.0
    assert float(jnp.max(jnp.abs(red8 - dense))) < 8 * scale + 1e-5

    # telescoping: what was reduced plus what every shard still carries
    # must equal the full EF-corrected sum (nothing lost, nothing doubled)
    for method, ratio in (("topk", 0.1), ("int8", 0.0)):
        red_m, err_m = make(method, ratio)(g, err)
        np.testing.assert_allclose(
            np.asarray(red_m + jnp.sum(err_m, axis=0)), np.asarray(dense),
            rtol=1e-4, atol=1e-4)
    print("compressed_allreduce parity ok")
    """)


def test_compressed_step_reduces_wire_bytes():
    """The compiled compressed train step must put strictly fewer bytes on
    the collective channels than the dense step, with no dense-gradient
    all-reduce left (only the O(4-byte) loss psum)."""
    _run("""
    import jax, jax.numpy as jnp
    from repro.config import TrainConfig
    from repro.core import build_glow_scanned
    from repro.core.distributions import flatten_state, std_normal_logpdf
    from repro.data import SyntheticImages
    from repro.dist.flow import shard_batch
    from repro.dist.step import make_dp_train_step
    from repro.optim import adamw_init, compression_init
    from repro.utils.hlo import collective_bytes

    x = SyntheticImages(size=8, batch=16, seed=0).batch_at(0)
    mesh = jax.make_mesh((8, 1), ("data", "model"))
    flow = build_glow_scanned(n_scales=2, k_steps=2, hidden=16,
                              grad_mode="coupled")
    params = flow.init(jax.random.PRNGKey(0), x)

    def loss_fn(p, b):
        z, logdet = flow.forward(p, b, None)
        d = flatten_state(z).shape[1]
        return -jnp.mean(std_normal_logpdf(z) + logdet) / d

    def bytes_for(method):
        cfg = TrainConfig(steps=4, grad_compression=method,
                          compression_ratio=0.01)
        err = (jax.tree_util.tree_map(lambda _: None, params)
               if method == "none" else compression_init(params, 8))
        state = {"params": jax.tree_util.tree_map(jnp.array, params),
                 "opt": adamw_init(params), "err": err}
        step = make_dp_train_step(loss_fn, cfg, mesh, state, x)
        hlo = step.lower(state, shard_batch(x, mesh),
                         jnp.asarray(0, jnp.int32)).compile().as_text()
        return collective_bytes(hlo)

    dense = bytes_for("none")
    assert dense["all-reduce"] > 10_000, dense
    for method in ("topk", "int8"):
        cb = bytes_for(method)
        assert cb["total"] < dense["total"], (method, cb, dense)
        assert cb["all-reduce"] <= 8, (
            method, "dense gradient all-reduce back on the wire", cb)
    print("wire bytes ok")
    """)


def test_overlap_vjp_step_matches_trailing_reduction():
    """A flow whose custom VJP psums cotangents over the data axis (the
    comm/compute-overlap path) must produce the same update as the same
    flow reduced by the step's explicit trailing psum."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.config import TrainConfig
    from repro.core import build_glow_scanned
    from repro.core.distributions import flatten_state, std_normal_logpdf
    from repro.data import SyntheticImages
    from repro.dist.flow import shard_batch
    from repro.dist.step import make_dp_train_step
    from repro.optim import adamw_init

    x = SyntheticImages(size=8, batch=16, seed=0).batch_at(0)
    mesh = jax.make_mesh((8, 1), ("data", "model"))

    def run(psum_axis):
        flow = build_glow_scanned(n_scales=2, k_steps=2, hidden=16,
                                  grad_mode="invertible",
                                  psum_axis=psum_axis)
        params = flow.init(jax.random.PRNGKey(0), x)

        def loss_fn(p, b):
            z, logdet = flow.forward(p, b, None)
            d = flatten_state(z).shape[1]
            return -jnp.mean(std_normal_logpdf(z) + logdet) / d

        err = jax.tree_util.tree_map(lambda _: None, params)
        state = {"params": params, "opt": adamw_init(params), "err": err}
        step = make_dp_train_step(
            loss_fn, TrainConfig(steps=4), mesh, state, x,
            grads_reduced_by_vjp=(flow.psum_axis == "data"))
        s, m = step(state, shard_batch(x, mesh), jnp.asarray(0, jnp.int32))
        return float(m["loss"]), s["params"]

    assert build_glow_scanned(n_scales=2, k_steps=2, hidden=16,
                              grad_mode="invertible",
                              psum_axis="data").psum_axis == "data"
    l1, p1 = run("data")   # overlapped: reduced inside the backward
    l2, p2 = run(None)     # trailing psum_cotangents
    assert abs(l1 - l2) < 1e-6
    pd = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), p1, p2)
    assert max(jax.tree_util.tree_leaves(pd)) < 1e-5
    print("overlap parity ok")
    """)


def test_int8_compressed_training_tracks_dense():
    """End-to-end: 8-shard training with int8-compressed collectives stays
    within quantization tolerance of the dense-reduction run."""
    _run("""
    import jax, numpy as np, tempfile
    from jax.sharding import Mesh
    from repro.config import TrainConfig
    from repro.core import build_glow_scanned
    from repro.data import SyntheticImages
    from repro.train.loop import train_flow

    data = SyntheticImages(size=8, batch=16, seed=0)
    ex = data.batch_at(0)
    flow = build_glow_scanned(n_scales=2, k_steps=2, hidden=16,
                              grad_mode="coupled")
    mesh = Mesh(np.array(jax.devices()).reshape(8, 1), ("data", "model"))

    def run(compression):
        cfg = TrainConfig(steps=6, lr=1e-3, warmup_steps=2,
                          checkpoint_every=100,
                          checkpoint_dir=tempfile.mkdtemp(),
                          grad_compression=compression)
        return train_flow(flow, data, cfg, ex, mesh=mesh)

    dense = run("none")
    int8 = run("int8")
    d = max(abs(a - b) for a, b in zip(dense.losses, int8.losses))
    assert d < 5e-3, f"int8 training diverged from dense: {d}"
    assert all(np.isfinite(run("topk").losses))
    print("compressed training ok")
    """)


def test_train_pipeline_learns():
    """GPipe mode: the microbatched schedule on a 4-stage ("pipe",) mesh
    backpropagates through scan + ppermute and reduces the loss."""
    _run("""
    import jax, jax.numpy as jnp, tempfile
    from repro.config import TrainConfig
    from repro.train.loop import train_pipeline

    mesh = jax.make_mesh((4,), ("pipe",))
    S, L_per, d = 4, 2, 16

    class Data:
        def batch_at(self, step):
            k = jax.random.PRNGKey(step % 4)
            x = jax.random.normal(k, (16, d))
            return {"x": x, "y": jnp.sin(x.sum(-1, keepdims=True))}

    def block_apply(p, h):
        return jnp.tanh(h @ p["w"] + p["b"])

    def init_fn():
        k = jax.random.PRNGKey(0)
        return {"stages": {"w": 0.3 * jax.random.normal(k, (S, L_per, d, d)),
                           "b": jnp.zeros((S, L_per, d))},
                "head": 0.1 * jax.random.normal(jax.random.PRNGKey(1), (d, 1))}

    def loss_head(params, h, batch):
        return jnp.mean((h @ params["head"] - batch["y"]) ** 2)

    cfg = TrainConfig(steps=20, lr=1e-2, warmup_steps=2, checkpoint_every=100,
                      checkpoint_dir=tempfile.mkdtemp(),
                      pipeline_microbatches=4)
    res = train_pipeline(block_apply, init_fn, Data(), cfg, mesh=mesh,
                         loss_head=loss_head, n_layers_per_stage=L_per)
    import numpy as np
    first = np.mean(res.losses[:4]); last = np.mean(res.losses[-4:])
    assert last < first - 0.01, f"no learning through the pipeline: {first} -> {last}"
    print("pipeline training ok")
    """, devices=4)


def test_elastic_restart_rezeros_compression_residuals():
    """Restarting compressed training on a different data-parallel width
    changes the per-shard residual shapes; the restore must re-zero them
    (they are optimization detail, not model state) instead of failing."""
    _run("""
    import warnings
    import jax, numpy as np, tempfile
    from jax.sharding import Mesh
    from repro.config import TrainConfig
    from repro.core import build_glow_scanned
    from repro.data import SyntheticImages
    from repro.train.loop import train_flow

    data = SyntheticImages(size=8, batch=16, seed=0)
    ex = data.batch_at(0)
    flow = build_glow_scanned(n_scales=2, k_steps=2, hidden=16,
                              grad_mode="coupled")
    ckdir = tempfile.mkdtemp()

    def cfg(steps):
        return TrainConfig(steps=steps, lr=1e-3, warmup_steps=2,
                           checkpoint_every=2, checkpoint_dir=ckdir,
                           grad_compression="int8")

    devs = np.array(jax.devices())
    mesh8 = Mesh(devs.reshape(8, 1), ("data", "model"))
    r1 = train_flow(flow, data, cfg(4), ex, mesh=mesh8)
    assert len(r1.losses) == 4

    mesh4 = Mesh(devs[:4].reshape(4, 1), ("data", "model"))
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        r2 = train_flow(flow, data, cfg(8), ex, mesh=mesh4)
    assert any("residuals re-zeroed" in str(x.message) for x in w), (
        [str(x.message) for x in w])
    assert r2.final_step == 7 and len(r2.losses) == 4  # resumed at step 4
    assert all(np.isfinite(r2.losses))
    print("elastic residual re-zero ok")
    """)
