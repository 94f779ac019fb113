"""The program's own trace names: host spans in the training loop and the
flow serving engine (``jax.profiler`` annotations), and device scopes in the
scanned GLOW step (``jax.named_scope``, carried into each op's name)."""

import glob
import os
import re

import jax
import jax.numpy as jnp

from repro.config import TrainConfig
from repro.core.glow_scan import build_glow_scanned
from repro.serve import FlowServeEngine
from repro.train import train_flow

SIZE, BATCH = 8, 2


class Images:
    """A step-indexed source of tiny RGB batches."""

    def batch_at(self, step: int):
        return jax.random.normal(jax.random.PRNGKey(step), (BATCH, SIZE, SIZE, 3))


def _flow():
    return build_glow_scanned(n_scales=2, k_steps=2, hidden=8, grad_mode="coupled")


def _cfg(tmp_path, steps: int, checkpoint_every: int) -> TrainConfig:
    return TrainConfig(steps=steps, lr=1e-3, warmup_steps=1,
                       checkpoint_every=checkpoint_every,
                       checkpoint_dir=str(tmp_path / "ckpt"), max_restarts=0)


def _spans(logdir: str, prefix: str) -> list:
    """``[(name, start_ns, end_ns, stats, thread)]`` of the host spans whose
    name starts with ``prefix``, in order of start."""
    (path,) = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for thread, line in enumerate(plane.lines):  # a line per thread
            for ev in line.events:
                if ev.name.startswith(prefix):
                    out.append((ev.name, ev.start_ns, ev.end_ns, dict(ev.stats), thread))
    return sorted(out, key=lambda s: s[1])


def test_train_flow_spans_each_step(tmp_path):
    x0 = Images().batch_at(0)
    # a first call checkpoints at step 1, so the traced call restores
    train_flow(_flow(), Images(), _cfg(tmp_path, 2, 2), x0)
    with jax.profiler.trace(str(tmp_path / "trace")):
        res = train_flow(_flow(), Images(), _cfg(tmp_path, 5, 5), x0)
    assert res.final_step == 4 and len(res.losses) == 3

    spans = _spans(str(tmp_path / "trace"), "repro.train")
    steps = {s[3]["step_num"]: s for s in spans if s[0] == "repro.train"}
    assert sorted(steps) == [2, 3, 4]
    assert all(s[3].get("_r") == 1 for s in steps.values())  # step markers
    loop_thread = steps[2][4]
    by_name: dict = {}
    for name, start, end, stats, thread in spans:
        by_name.setdefault(name, []).append(stats["step_num"])
        if name in ("repro.train", "repro.train.prefetch", "repro.train.restore"):
            continue
        # every child of the loop lies inside its own step's span
        _, p_start, p_end, _, p_thread = steps[stats["step_num"]]
        assert thread == p_thread == loop_thread
        assert p_start <= start and end <= p_end, name
    for name in ("repro.train.fetch", "repro.train.dispatch", "repro.train.readback"):
        assert by_name[name] == [2, 3, 4], name
    assert by_name["repro.train.build"] == [2]  # once per call
    assert by_name["repro.train.checkpoint"] == [4]  # checkpoint_every=5
    assert by_name["repro.train.restore"] == [1]  # the step it restored
    # the prefetch thread fetches ahead on a thread of its own
    prefetch = [s for s in spans if s[0] == "repro.train.prefetch"]
    assert {2, 3, 4} <= {s[3]["step_num"] for s in prefetch}
    assert all(s[4] != loop_thread for s in prefetch)


def test_flow_serve_engine_spans_each_request(tmp_path):
    flow = _flow()
    x = Images().batch_at(0)
    params = flow.init(jax.random.PRNGKey(0), x)
    engine = FlowServeEngine(flow, params)
    like = jax.eval_shape(lambda p, v: flow.forward(p, v)[0], params, x)
    jax.block_until_ready(engine.sample(jax.random.PRNGKey(1), like))  # compiles
    with jax.profiler.trace(str(tmp_path / "trace")):
        for i in range(2):
            jax.block_until_ready(engine.sample(jax.random.PRNGKey(2 + i), like))
        jax.block_until_ready(engine.log_prob(x))

    got = [(s[0], s[3]["request"]) for s in _spans(str(tmp_path / "trace"), "repro.serve")]
    assert got == [("repro.serve.latents", 1), ("repro.serve.place", 1),
                   ("repro.serve.dispatch", 1),
                   ("repro.serve.latents", 2), ("repro.serve.place", 2),
                   ("repro.serve.dispatch", 2),
                   ("repro.serve.place", 3), ("repro.serve.dispatch", 3)]


def test_glow_step_ops_carry_scope_names(tmp_path, monkeypatch):
    """The training step's ops, through the Pallas kernels' path and the
    reversible backward, are named by the program's scopes."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("REPRO_COUPLED_BWD", "reversible")
    x0 = Images().batch_at(0)
    res = train_flow(_flow(), Images(), _cfg(tmp_path, 1, 1), x0)
    state = {"params": res.params, "opt": res.opt_state,
             "err": jax.tree_util.tree_map(lambda _: None, res.params)}
    text = res.step_fn.lower(state, x0, jnp.asarray(1, jnp.int32)).as_text(debug_info=True)
    # an op's name is its scopes, then its primitive: "a/transpose(jvp(b))/conv"
    paths = [name.split("/") for name in re.findall(r'loc\("([^"]*)"', text)]
    scopes = {part for path in paths for part in path[:-1]}
    assert "transpose(jvp(conditioner))" in scopes  # the conditioner's VJP
    bare = {re.sub(r"^(\w+\()+|\)+$", "", part) for part in scopes}
    assert {"conditioner", "kernel_layout", "optimizer", "actnorm", "conv1x1", "squeeze",
            "loss"} <= bare
    # the kernels keep the names the trace shows
    for kernel in ("flowstep_fwd", "coupling_bwd", "spine_bwd"):
        assert [kernel, "pallas_call"] in [path[-2:] for path in paths], kernel
