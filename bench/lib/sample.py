"""Sampling cells: a closed loop of one client asking the program's
``FlowServeEngine.sample`` for a fixed number of draws per request.

Each request takes a new key from the seed.  Once the window has closed, a
sample of the finished requests, drawn from the seed, is checked against the
plain reference's inverse of the same latents.
"""

from __future__ import annotations

import contextlib
import math
import random
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import synthetic
from bench.lib import weights as W
from bench.reference.glow import HIGHEST, Glow

FAULTS = ("altered",)


def latents(key, like):
    """The latents ``FlowServeEngine.sample(key, like)`` draws: its
    published stream, ``fold_in(split(key)[1], 0)`` split once per latent."""
    leaves, treedef = jax.tree_util.tree_flatten(like)
    stream = jax.random.fold_in(jax.random.split(key, 2)[1], 0)
    keys = jax.random.split(stream, len(leaves))
    return jax.tree_util.tree_unflatten(
        treedef, [jax.random.normal(k, v.shape, v.dtype) for k, v in zip(keys, leaves)])


def _span(on: bool, name: str):
    return jax.profiler.TraceAnnotation(name) if on else contextlib.nullcontext()


class Run:
    def __init__(self, cell, seed: int, fault=None):
        if fault is not None and fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}")
        self.cell, self.seed, self.fault = cell, seed, fault
        self.model, self.traffic = cell.model, cell.traffic
        self.size, self.draws = self.traffic["image_size"], self.traffic["draws"]
        self.key_w, self.key_req = jax.random.split(synthetic.key_from_seed(seed))

    def setup(self):
        from repro.serve import FlowServeEngine

        flow = W.build_flow(self.model)
        params = jax.jit(lambda k: W.to_program(*W.make(k, self.model, self.size)))(self.key_w)
        x_like = jax.ShapeDtypeStruct((self.draws, self.size, self.size,
                                       self.model["channels"]), jnp.float32)
        self.like = jax.eval_shape(lambda p, x: flow.forward(p, x)[0], params, x_like)
        self.engine = FlowServeEngine(flow, params)
        fold = jax.jit(jax.random.fold_in)
        self.key_of = lambda i: fold(self.key_req, jnp.uint32(i))
        # warm-up: one request on a key no window request uses
        jax.block_until_ready(self.serve(self.key_of(2**32 - 1)))

    def serve(self, key):
        out = self.engine.sample(key, self.like)
        if self.fault == "altered":  # one draw of every answer changed
            out = out.at[0].multiply(1.5)
        return out

    def window(self, seconds: float, spans: bool = False, on_open=None, on_close=None) -> dict:
        keep = self.traffic["check_requests"]
        rng = random.Random(self.seed)
        kept, latencies = [], []
        n = 0
        if on_open is not None:
            on_open()
        t0 = time.perf_counter()
        while True:
            with _span(spans, "bench.prepare"):
                key = self.key_of(n)
            t_issue = time.perf_counter()
            with _span(spans, "bench.dispatch"):
                out = self.serve(key)
            with _span(spans, "bench.wait"):
                out.block_until_ready()
            t_done = time.perf_counter()
            latencies.append(t_done - t_issue)
            # reservoir sample of the finished requests, drawn from the seed
            if len(kept) < keep:
                kept.append((n, out))
            else:
                j = rng.randrange(n + 1)
                if j < keep:
                    kept[j] = (n, out)
            n += 1
            if t_done - t0 >= seconds:
                break
        if on_close is not None:
            on_close()
        self.kept = kept
        return {"units": n, "seconds": t_done - t0, "draws": n * self.draws,
                "latencies": latencies}

    def numbers(self, pairs: dict) -> dict:
        return compare(pairs)

    def release(self):
        self.engine = None
        self.kept = [(i, np.asarray(out)) for i, out in self.kept]

    def control(self, ref: dict) -> dict:
        """The numbers of the control: the reference in bfloat16 at the
        default precision, in the program's place."""
        control = self.reference(jnp.bfloat16, jax.lax.Precision.DEFAULT)
        return compare({i: (control[i][1], ref[i][1]) for i in ref})

    def reference(self, dtype=jnp.float32, precision=HIGHEST) -> dict:
        """The reference's inverse of each kept request's latents:
        ``{request: (program's answer, reference's answer)}``."""
        glow = Glow(self.model, dtype, precision)
        w, bufs = jax.jit(lambda k: W.make(k, self.model, self.size))(self.key_w)
        inverse = jax.jit(lambda w, bufs, z: glow.inverse(glow.cast(w), bufs, z)
                          .astype(jnp.float32))
        return {i: (out, np.asarray(inverse(w, bufs, latents(self.key_of(i), self.like))))
                for i, out in self.kept}


def compare(pairs: dict) -> dict:
    """The widest gap of any kept draw's pixel from the reference, against
    the larger of 1 and the reference's largest pixel."""
    gap = 0.0
    for out, ref in pairs.values():
        gap = max(gap, float(np.max(np.abs(out - ref)) / max(1.0, float(np.max(np.abs(ref))))))
    return {"sample_gap": gap}


def work(cell) -> dict:
    """Per request: the model's operations, and the flow kernels' least work."""
    from bench.work.glow import flow_kernel_work, model_flops_per_example

    t = cell.traffic
    ops, byts = flow_kernel_work(cell.model, t["image_size"], t["draws"], "sample")
    return {"model_flops": model_flops_per_example(cell.model, t["image_size"]) * t["draws"],
            "kernel_flops": ops, "kernel_bytes": byts}


def checked_run(cell, seed: int, seconds: float, fault=None) -> Run:
    """A run that has made the readings the check compares (a window of
    ``seconds`` of requests), with the program's state released."""
    run = Run(cell, seed, fault=fault)
    run.setup()
    run.window(seconds)
    run.release()
    return run


def calibration_faults(cell) -> list:
    return ["altered"]


def end_to_end(win: dict) -> dict:
    lat = sorted(win["latencies"])
    return {"draws_per_s": win["draws"] / win["seconds"],
            "request_p95_ms": 1e3 * lat[math.ceil(0.95 * len(lat)) - 1]}
