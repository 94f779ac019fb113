"""Train GLOW on synthetic images with the full training substrate
(checkpointing, restart, cosine schedule) in memory-frugal mode.

    PYTHONPATH=src python examples/train_glow.py [--size 32] [--steps 150]

This is the paper's flagship workload (Figs. 1-2): the same script scales to
large images because gradient memory is depth-independent — switch
``--grad-mode autodiff`` to watch the naive-AD baseline blow up instead.
"""

import argparse

import jax

from repro.config import TrainConfig
from repro.core import build_glow, build_glow_scanned, nll_bits_per_dim
from repro.data import SyntheticImages
from repro.train import train_flow
from repro.utils.cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", type=int, default=32)
    ap.add_argument("--steps", type=int, default=150)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--grad-mode", default="invertible",
                    choices=["invertible", "coupled", "autodiff"])
    ap.add_argument(
        "--scanned", action="store_true",
        help="scan-compiled GLOW through the fused flow-step megakernel"
             " (O(1)-in-depth tracing; the coupled fast path — §Perf/H2)",
    )
    ap.add_argument("--ckpt", default="checkpoints/glow")
    args = ap.parse_args()
    enable_compile_cache()

    build = build_glow_scanned if args.scanned else build_glow
    flow = build(n_scales=2, k_steps=4, hidden=32, grad_mode=args.grad_mode)
    data = SyntheticImages(size=args.size, batch=args.batch, seed=0)
    tcfg = TrainConfig(
        steps=args.steps, lr=1e-3, warmup_steps=10,
        checkpoint_every=50, checkpoint_dir=args.ckpt,
    )
    res = train_flow(flow, data, tcfg, example=data.batch_at(0), log_every=25)
    print(f"finished at step {res.final_step}; "
          f"loss {res.losses[0]:.3f} -> {res.losses[-1]:.3f}")

    params = res.params
    bpd = nll_bits_per_dim(flow, params, data.batch_at(999))
    print(f"held-out bits/dim: {float(bpd):.3f}")
    # sample by inversion
    import jax.numpy as jnp

    state, _ = flow.forward(params, data.batch_at(0))
    z = jax.tree_util.tree_map(
        lambda v: jax.random.normal(jax.random.PRNGKey(1), v.shape, v.dtype) * 0.7,
        state,
    )
    imgs = flow.inverse(params, z)
    print("sampled image tensor:", imgs.shape,
          "range", float(jnp.min(imgs)), float(jnp.max(imgs)))


if __name__ == "__main__":
    main()
