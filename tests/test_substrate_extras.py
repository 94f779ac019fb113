"""Tests for the scale-out substrate extras: async checkpointing, data
prefetch, gradient accumulation."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import get_arch
from repro.data import SyntheticTokens
from repro.data.pipeline import Prefetcher
from repro.models import build_model
from repro.optim.accum import accumulate_grads
from repro.train.async_ckpt import AsyncCheckpointer
from repro.train import checkpoint as ckpt


def test_async_checkpointer_roundtrip(tmp_path):
    state = {"a": jnp.arange(16.0), "b": {"c": jnp.ones((4, 4))}}
    acp = AsyncCheckpointer(str(tmp_path), keep=2)
    for step in (1, 2, 3):
        acp.save(jax.tree_util.tree_map(lambda v: v * step, state), step)
    acp.wait()
    assert acp.completed == [1, 2, 3]
    restored, step = ckpt.restore(state, str(tmp_path))
    assert step == 3
    np.testing.assert_allclose(np.asarray(restored["a"]), np.arange(16.0) * 3)


def test_async_checkpointer_snapshot_isolation(tmp_path):
    """The saved state must be the value at save() time, not at write time."""
    acp = AsyncCheckpointer(str(tmp_path))
    state = {"x": jnp.zeros(4)}
    acp.save(state, 1)
    state = {"x": jnp.ones(4)}  # mutate after handing off
    acp.wait()
    restored, _ = ckpt.restore(state, str(tmp_path))
    np.testing.assert_allclose(np.asarray(restored["x"]), np.zeros(4))


def test_prefetcher_matches_direct_and_is_ordered():
    data = SyntheticTokens(100, seq_len=8, batch=4, seed=3)
    pf = Prefetcher(data.batch_at, start_step=5, lookahead=3)
    try:
        for expect in (5, 6, 7, 8):
            step, batch = pf.get()
            assert step == expect
            ref = data.batch_at(step)
            np.testing.assert_array_equal(
                np.asarray(batch["tokens"]), np.asarray(ref["tokens"])
            )
    finally:
        pf.close()


def test_grad_accumulation_matches_full_batch():
    spec = get_arch("yi-6b")
    model, cfg = build_model(spec.reduced, dtype="float32", residual_dtype="float32")
    params = model.init(jax.random.PRNGKey(0))
    data = SyntheticTokens(cfg.vocab_size, 16, 8, seed=0)
    batch = data.batch_at(0)

    def loss_fn(p, b):
        return model.train_loss(p, b)

    loss_full, _, g_full = accumulate_grads(loss_fn, params, batch, 1)
    loss_acc, _, g_acc = accumulate_grads(loss_fn, params, batch, 4)
    # microbatch losses average over micro dims; token counts equal per slice
    assert abs(float(loss_full) - float(loss_acc)) < 5e-3
    diffs = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b.astype(a.dtype)))), g_acc, g_full
    )
    assert max(jax.tree_util.tree_leaves(diffs)) < 5e-3


def test_topk_compression_sends_exactly_k_under_ties():
    """A threshold rule (|g| >= thresh) sends *every* tied entry — a
    constant gradient would ship the whole tensor at ratio 0.25.  The
    selection must be exactly-k regardless of ties."""
    from repro.optim.compression import compress_grads, compression_init

    g = {"w": jnp.ones((10, 10))}  # all 100 magnitudes tie
    err = compression_init(g)
    sent, new_err = compress_grads(g, err, "topk", ratio=0.25)
    n_sent = int(jnp.sum(sent["w"] != 0.0))
    assert n_sent == 25, f"tie-broken top-k sent {n_sent} entries, not k=25"
    # error feedback: what was not sent is carried, exactly
    np.testing.assert_allclose(
        np.asarray(sent["w"] + new_err["w"]), np.asarray(g["w"]), rtol=1e-6
    )


def test_prefetcher_close_is_prompt_and_joins_worker():
    """Shutdown race regression: a worker blocked in ``queue.put`` must
    observe the stop flag — close() returns with the thread joined even
    when the queue is full and the producer mid-put."""
    data = SyntheticTokens(100, seq_len=8, batch=4, seed=3)
    pf = Prefetcher(data.batch_at, start_step=0, lookahead=2)
    pf.get()  # ensure the worker is alive and producing
    time.sleep(0.1)  # let the worker fill the queue and block in put
    t0 = time.perf_counter()
    pf.close()
    assert time.perf_counter() - t0 < 2.0, "close() stalled on a blocked put"
    assert not pf._thread.is_alive(), "worker thread not joined"
    with pytest.raises(RuntimeError):
        pf.get()
    pf.close()  # idempotent


def test_prefetcher_surfaces_worker_errors():
    def bad_batch(step):
        if step >= 2:
            raise ValueError("source exhausted")
        return step

    pf = Prefetcher(bad_batch, start_step=0, lookahead=1)
    try:
        assert pf.get() == (0, 0)
        assert pf.get() == (1, 1)
        with pytest.raises(ValueError, match="source exhausted"):
            pf.get()
    finally:
        pf.close()


_CACHE_PROBE = """
import sys
import jax, jax.numpy as jnp
from repro.utils import cache

cache.COMPILE_CACHE_DIR = cache.Path(sys.argv[1])  # stands in for the checkout's
hits = []
jax.monitoring.register_event_listener(
    lambda event, **_: hits.append(event)
    if event == "/jax/compilation_cache/cache_hits" else None)
print(cache.enable_compile_cache())
jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64))).block_until_ready()
print(len(hits))
"""


@pytest.mark.parametrize("env_dir", [False, True])
def test_compile_cache_location_and_reuse(tmp_path, env_dir):
    """``enable_compile_cache``: with ``JAX_COMPILATION_CACHE_DIR`` set the
    cache goes there and nowhere else; unset, to the fixed checkout dir.
    Either way a second identical run compiles from the cache."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    default, chosen = tmp_path / "default", tmp_path / "chosen"
    env = dict(os.environ, PYTHONPATH=os.path.join(repo, "src"),
               JAX_PLATFORMS="cpu", JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(chosen)
    expect = chosen if env_dir else default
    runs = []
    for _ in range(2):
        out = subprocess.run(
            [sys.executable, "-c", _CACHE_PROBE, str(default)],
            capture_output=True, text=True, env=env, timeout=300,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        runs.append(out.stdout.split())
    assert runs[0][0] == str(expect)
    assert any(expect.iterdir())
    if env_dir:
        assert not default.exists()
    assert int(runs[0][1]) == 0 and int(runs[1][1]) >= 1
