"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch yi-6b --reduced \
        --steps 50 --seq 128 --batch 8 [--grad-mode coupled] [--mesh d,m]

    PYTHONPATH=src python -m repro.launch.train --scenario lg-smoke \
        --ckpt checkpoints/uq [--steps 50] [--mesh auto]

On a real cluster this process runs per host under the job scheduler
(restart-on-failure is handled by the in-loop supervisor + checkpoints);
``--mesh`` shards the step over the local devices via the same sharding
rules as the production dry-run.  ``--scenario`` trains a named
``repro.uq`` uncertainty-quantification scenario (amortized posterior or
image-prior flow) instead of an LM; serve the result with
``repro.launch.serve --scenario``.
"""

from __future__ import annotations

import argparse

import jax

from repro.config import ShapeSpec, TrainConfig, get_arch
from repro.data import SyntheticTokens
from repro.models import build_model
from repro.train import train_lm
from repro.utils.cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    group = ap.add_mutually_exclusive_group(required=True)
    group.add_argument("--arch", help="LM architecture id (repro.configs)")
    group.add_argument("--scenario",
                       help="repro.uq scenario name (amortized posterior /"
                            " image-prior flow training)")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale config of the same family")
    ap.add_argument("--steps", type=int, default=0,
                    help="override step count (0 = arch default 100 /"
                         " scenario recipe)")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--grad-mode", default=None,
                    choices=[None, "invertible", "coupled", "remat", "autodiff"])
    ap.add_argument("--grad-compression", default="none",
                    choices=["none", "topk", "int8"])
    ap.add_argument("--accum", type=int, default=1,
                    help="gradient-accumulation microbatches per (per-shard)"
                         " step (1 = off)")
    ap.add_argument("--prefetch", type=int, default=2,
                    help="input batches prefetched (and placed) ahead of the"
                         " running step (0 = synchronous)")
    ap.add_argument("--ckpt", default="checkpoints/train")
    ap.add_argument("--step-timeout", type=float, default=0.0)
    ap.add_argument("--mesh", default="",
                    help="'auto' (largest (data, model) factoring of the "
                         "device count) or 'd,m'; empty = single-device")
    args = ap.parse_args()
    enable_compile_cache()

    from repro.launch.mesh import parse_mesh_arg

    mesh = parse_mesh_arg(args.mesh)

    if args.scenario:
        from repro.uq.scenarios import get_scenario, train_scenario

        sc = get_scenario(args.scenario)
        kind = "amortized posterior" if sc.conditional else "image prior"
        print(f"scenario={sc.name} ({kind}) flow={sc.flow.name} "
              f"steps={args.steps or sc.steps} devices={jax.device_count()}")
        run = train_scenario(
            sc, steps=args.steps or None, mesh=mesh, ckpt_dir=args.ckpt,
            log_every=max((args.steps or sc.steps) // 10, 1),
        )
        res = run.result
        if res.losses:
            print(f"done at step {res.final_step}: loss {res.losses[0]:.4f}"
                  f" -> {res.losses[-1]:.4f}; restarts={res.restarts}; "
                  f"checkpoints in {args.ckpt}")
        else:  # resumed a checkpoint already at the final step
            print(f"nothing to do: checkpoint in {args.ckpt} already at "
                  f"step {res.final_step}")
        return

    spec = get_arch(args.arch)
    cfg_model = spec.reduced if args.reduced else spec.config
    model, cfg = build_model(cfg_model)
    mesh_desc = (
        "x".join(map(str, mesh.devices.shape)) if mesh is not None else "none"
    )
    print(f"arch={cfg.name} params~{cfg.param_count()/1e6:.1f}M "
          f"reversible={cfg.reversible} devices={jax.device_count()} "
          f"mesh={mesh_desc}")

    steps = args.steps or 100
    data = SyntheticTokens(cfg.vocab_size, args.seq, args.batch, seed=0)
    tcfg = TrainConfig(
        steps=steps, lr=args.lr, warmup_steps=max(steps // 20, 2),
        checkpoint_every=max(steps // 4, 10), checkpoint_dir=args.ckpt,
        grad_compression=args.grad_compression, step_timeout_s=args.step_timeout,
        accum_steps=args.accum, prefetch=args.prefetch,
    )
    res = train_lm(model, data, tcfg, grad_mode=args.grad_mode, mesh=mesh,
                   log_every=max(steps // 10, 1))
    print(f"done at step {res.final_step}: loss {res.losses[0]:.4f} -> "
          f"{res.losses[-1]:.4f}; restarts={res.restarts}; "
          f"straggler flags={len(res.flagged_steps)}")


if __name__ == "__main__":
    main()
