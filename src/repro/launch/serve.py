"""Serving launcher: batched generation with prefill + jitted decode, or a
trained ``repro.uq`` scenario's posterior service.

    PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --reduced \
        --batch 4 --prompt-len 16 --max-new 32

    PYTHONPATH=src python -m repro.launch.serve --scenario lg-smoke \
        --ckpt checkpoints/uq [--samples 20000] [--mesh auto] [--no-calibration]

The scenario path restores the scenario's checkpoint, streams posterior
statistics for a held-out observation through ``PosteriorEngine`` (never
materializing the draw cloud; batch-sharded over ``--mesh``), and prints
the SBC/coverage calibration report.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.config import get_arch
from repro.models import build_model
from repro.serve import ServeEngine
from repro.train import checkpoint as ckpt
from repro.utils.cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    group = ap.add_mutually_exclusive_group(required=True)
    group.add_argument("--arch", help="LM architecture id (repro.configs)")
    group.add_argument("--scenario",
                       help="repro.uq scenario to serve (posterior"
                            " statistics + calibration from --ckpt)")
    ap.add_argument("--samples", type=int, default=0,
                    help="posterior draws to stream (0 = scenario default)")
    ap.add_argument("--chunk", type=int, default=0,
                    help="streaming chunk size (0 = scenario default)")
    ap.add_argument("--no-calibration", action="store_true",
                    help="skip the SBC/coverage calibration pass")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--ckpt", default="", help="restore params from checkpoint dir")
    ap.add_argument("--mesh", default="",
                    help="'auto' or 'd,m': shard params/caches over a "
                         "(data, model) mesh of the local devices")
    args = ap.parse_args()
    enable_compile_cache()

    from repro.launch.mesh import parse_mesh_arg

    mesh = parse_mesh_arg(args.mesh)

    if args.scenario:
        if not args.ckpt:
            ap.error("--scenario serving needs --ckpt (a directory written "
                     "by repro.launch.train --scenario)")
        from repro.uq.scenarios import posterior_report, restore_scenario

        run = restore_scenario(args.scenario, args.ckpt, mesh=mesh)
        if not run.scenario.conditional:
            # prior scenario: batch-sharded sample statistics only
            from repro.serve import FlowServeEngine
            from repro.uq.posterior import PosteriorEngine

            data_like = jax.eval_shape(
                lambda p: run.model.forward(p, jnp.zeros(
                    (run.scenario.batch, run.scenario.image_size,
                     run.scenario.image_size, 3))),
                run.params,
            )[0]
            engine = FlowServeEngine(run.model, run.params, mesh=mesh)
            size = run.scenario.image_size
            pe = PosteriorEngine(engine, theta_like=data_like,
                                 theta_shape=(size, size, 3))
            stats = pe.run(jax.random.PRNGKey(0),
                           n_samples=args.samples or 2048,
                           chunk=args.chunk or run.scenario.batch * 16)
            print(stats.summary())
            return
        t0 = time.time()
        stats, report = posterior_report(
            run,
            n_samples=args.samples or None,
            chunk=args.chunk or None,
            calibration=not args.no_calibration,
        )
        dt = time.time() - t0
        print(stats.summary())
        print(f"streamed {stats.n} draws in {dt:.2f}s "
              f"({stats.n / dt:.0f} draws/s incl. compile)")
        if report is not None:
            print(report.summary())
        return

    spec = get_arch(args.arch)
    model, cfg = build_model(spec.reduced if args.reduced else spec.config)
    rng = jax.random.PRNGKey(0)
    params = model.init(rng)
    if args.ckpt:
        like = {"params": params}
        state, step = ckpt.restore(like, args.ckpt)
        params = state["params"]
        print(f"restored step {step} from {args.ckpt}")

    engine = ServeEngine(model, params, max_len=args.prompt_len + args.max_new,
                         temperature=args.temperature, mesh=mesh)
    prompt = {
        "tokens": jax.random.randint(
            rng, (args.batch, args.prompt_len), 0, cfg.vocab_size, jnp.int32
        )
    }
    if cfg.frontend is not None and cfg.frontend.kind == "vision":
        from repro.models.frontends import VISION_EMBED_DIM

        prompt["patches"] = jax.random.normal(
            rng, (args.batch, cfg.frontend.n_patches, VISION_EMBED_DIM),
            jnp.dtype(cfg.dtype),
        )
    if cfg.is_enc_dec:
        prompt["frames"] = jax.random.normal(
            rng, (args.batch, cfg.frontend.n_frames, cfg.d_model), jnp.dtype(cfg.dtype)
        )

    t0 = time.time()
    toks, _ = engine.generate(prompt, max_new=args.max_new)
    dt = time.time() - t0
    n_new = toks.shape[0] * toks.shape[1]
    print(f"generated {toks.shape} tokens in {dt:.2f}s "
          f"({n_new/dt:.1f} tok/s incl. compile)")
    print(toks[:, :16])


if __name__ == "__main__":
    main()
