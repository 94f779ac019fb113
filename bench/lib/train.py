"""Training cells: closed-loop GLOW training through the program's own loop.

Every step runs inside ``repro.train.train_flow``, with the loop's batch
prefetch, its per-step loss read-back and its donated state.  The loop's
per-step hook (its ``injector``, called on the main thread before each step,
once the previous step's loss has been read back) stamps the window, and the
loop's own preemption path (SIGTERM: checkpoint, then return) ends a call.

Set-up makes the weights and a pool of distinct batches from the seed, then
calls ``train_flow`` twice: step 0, then steps 1-2, each call resuming from
the checkpoint the last one wrote, with one configuration and so one
compiled step.  The window's call resumes at step 3.  Its first two steps
each load a program: step 3 the one for the state restored from the
checkpoint, step 4 the one for the state a step returns (``jax.jit`` keys
the two apart).  The window opens once both are done.  The correctness
numbers come from those first three steps: each step's loss, the first
gradient as AdamW holds it after step 0, and each leaf's change over the
three steps, against the plain reference.
"""

from __future__ import annotations

import signal
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import synthetic
from bench.lib import weights as W
from bench.reference.glow import HIGHEST, Glow, adamw

#: faults a test or a calibration run can plant in the timed path
FAULTS = ("frozen", "half_batch", "no_exchange")
#: the step at which the window's call resumes
WINDOW_CALL = 3
#: the first step of the window: the call's first two steps load programs
WINDOW_FIRST = WINDOW_CALL + 2


class SeededFlow:
    """The program's flow, initialised with the benchmark's weights.  A
    ``fault`` plants a broken step for the tests and the calibration:
    ``frozen`` (no gradient reaches the weights), ``half_batch`` (the loss
    is the mean over half of the batch), ``no_exchange`` (the flow does not
    reduce its gradients over the chips, yet says that it does)."""

    def __init__(self, flow, params, fault=None, psum_axis=None):
        self.flow, self.params, self.fault = flow, params, fault
        self.psum_axis = psum_axis

    def init(self, rng, x, cond=None):
        # the first call starts training from these weights (and the step
        # donates them); a resuming call reads only their shapes and types
        params, self.params = self.params, jax.tree_util.tree_map(
            lambda v: np.zeros(v.shape, v.dtype), self.params)
        return params

    def forward(self, params, x, cond=None):
        if self.fault == "frozen":
            params = jax.lax.stop_gradient(params)
        elif self.fault == "half_batch":
            x = x[: x.shape[0] // 2]
        return self.flow.forward(params, x, cond)


class Pool:
    """The loop's data source: the pool's batches in turn."""

    def __init__(self, batches, spans: bool = False):
        self.batches, self.spans = batches, spans

    def batch_at(self, step: int):
        if self.spans:  # on the loop's prefetch thread
            with jax.profiler.TraceAnnotation("bench.feed"):
                return self.batches[step % len(self.batches)]
        return self.batches[step % len(self.batches)]


def _preempt():
    """The loop's preemption: its SIGTERM handler checkpoints and returns
    once the running step is done."""
    if not callable(signal.getsignal(signal.SIGTERM)):
        raise RuntimeError("train_flow installed no SIGTERM handler")
    signal.raise_signal(signal.SIGTERM)


class StopAfter:
    """A hook that ends the loop's call after ``step``."""

    def __init__(self, step: int):
        self.step = step

    def maybe_fail(self, step: int):
        if step == self.step:
            _preempt()


class Window:
    """A hook that measures the window: it opens before step ``first`` and
    closes before the first step that starts ``seconds`` or more later, so
    it holds whole steps, each with its loss read back.  The loop runs that
    last step too, after the window, and then returns."""

    def __init__(self, first: int, seconds: float, spans: bool, on_open, on_close):
        self.first, self.seconds, self.spans = first, seconds, spans
        self.on_open, self.on_close = on_open, on_close
        self.t0 = self.t1 = None
        self.steps = 0
        self.span = None

    def _span(self, open_next: bool):
        if self.span is not None:
            self.span.__exit__(None, None, None)
            self.span = None
        if open_next and self.spans:  # the loop's host work around one step
            self.span = jax.profiler.TraceAnnotation("bench.loop")
            self.span.__enter__()

    def maybe_fail(self, step: int):
        if step < self.first or self.t1 is not None:
            return
        if self.t0 is None:
            self.on_open()
            self._span(True)
            self.t0 = time.perf_counter()
            return
        now = time.perf_counter()
        if now - self.t0 < self.seconds:
            self._span(True)
            return
        self.t1, self.steps = now, step - self.first
        self._span(False)
        self.on_close()
        _preempt()


class Run:
    def __init__(self, cell, seed: int, fault=None):
        if fault is not None and fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}")
        self.cell, self.fault = cell, fault
        self.model, self.traffic = cell.model, cell.traffic
        self.recipe = self.traffic["recipe"]
        self.size, self.batch = self.traffic["image_size"], self.traffic["batch"]
        self.key_w, self.key_data = jax.random.split(synthetic.key_from_seed(seed))
        self.mesh = None
        if cell.chips > 1:
            from repro.launch.mesh import make_auto_mesh

            self.mesh = make_auto_mesh((cell.chips, 1))

    def _shardings(self):
        from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

        if self.mesh is None:
            one = SingleDeviceSharding(jax.devices()[0])
            return one, one
        from repro.dist.sharding import batch_sharding

        return batch_sharding(self.mesh), NamedSharding(self.mesh, PartitionSpec())

    def _config(self):
        from repro.config import TrainConfig

        r = self.recipe
        # one configuration for every call: the schedule's horizon is also
        # the loop's, far beyond any window, so every call ends by preemption
        return TrainConfig(
            steps=r["decay_steps"], lr=r["lr"], warmup_steps=r["warmup_steps"],
            weight_decay=r["weight_decay"], grad_clip=r["grad_clip"],
            b1=r["b1"], b2=r["b2"], eps=r["eps"], checkpoint_every=r["decay_steps"],
            checkpoint_dir=self.ckpt.name, keep_checkpoints=1, max_restarts=0,
        )

    def _train(self, hook, spans: bool = False):
        from repro.train import train_flow

        return train_flow(self.flow, Pool(self.pool, spans), self._config(), self.pool[0],
                          mesh=self.mesh, injector=hook)

    # -- set-up: the program's first three steps -----------------------------
    def setup(self):
        batch_sh, rep = self._shardings()
        dp = self.mesh is not None
        flow = W.build_flow(self.model, psum_axis="data" if dp and self.fault != "no_exchange"
                            else None)
        self.pool = synthetic.image_pool(self.key_data, self.traffic["pool"], self.batch,
                                         self.size, batch_sh)
        params = jax.jit(lambda k: W.to_program(*W.make(k, self.model, self.size)),
                         out_shardings=rep)(self.key_w)
        W.check_layout(flow, params, self.pool[0])
        # the loop skips its own gradient reduction where the flow says
        # that its backward reduces; the fault says so falsely
        self.flow = SeededFlow(flow, params, self.fault,
                               psum_axis="data" if self.fault == "no_exchange"
                               else flow.psum_axis)
        del params
        self.ckpt = tempfile.TemporaryDirectory()
        first = self._train(StopAfter(0))
        # AdamW's first moment after one step from zero is (1 - b1) * g
        norms = jax.jit(lambda t: W.step_norms(W.from_program(t)))
        grad = {k: np.asarray(v) / (1.0 - self.recipe["b1"])
                for k, v in norms(first.opt_state["mu"]).items()}
        losses = list(first.losses)
        del first
        then = self._train(StopAfter(WINDOW_CALL - 1))
        change = jax.jit(lambda p, k: W.step_norms(jax.tree_util.tree_map(
            jnp.subtract, W.from_program(p), W.make(k, self.model, self.size)[0])))
        update = {k: np.asarray(v) for k, v in change(then.params, self.key_w).items()}
        self.program = {"loss": losses + list(then.losses), "grad": grad, "update": update}

    # -- the measured window -------------------------------------------------
    def window(self, seconds: float, spans: bool = False, on_open=None, on_close=None) -> dict:
        hook = Window(WINDOW_FIRST, seconds, spans, on_open or (lambda: None),
                      on_close or (lambda: None))
        res = self._train(hook, spans)
        jax.block_until_ready(res.params)
        if hook.t1 is None:
            raise RuntimeError("the loop returned before the window closed")
        # the window's steps, each with its loss; one that is not finite failed
        losses = res.losses[WINDOW_FIRST - WINDOW_CALL:][:hook.steps]
        return {"units": hook.steps, "seconds": hook.t1 - hook.t0,
                "samples": hook.steps * self.batch,
                "failed": int(sum(not np.isfinite(v) for v in losses)),
                "last_loss": losses[-1]}

    def numbers(self, ref: dict) -> dict:
        return compare(self.program, ref)

    def release(self):
        """Drop the program's state, so the reference has the memory."""
        self.flow = self.pool = None
        self.ckpt.cleanup()

    # -- the reference -------------------------------------------------------
    def reference(self, dtype=jnp.float32, precision=HIGHEST) -> dict:
        """The reference's readings of the same three steps, on one chip,
        in blocks of at most ``reference_block`` rows."""
        one = jax.sharding.SingleDeviceSharding(jax.devices()[0])
        w, bufs = jax.jit(lambda k: W.make(k, self.model, self.size),
                          out_shardings=one)(self.key_w)
        pool = synthetic.image_pool(self.key_data, self.traffic["pool"], self.batch,
                                    self.size, one)
        return reference_steps(self.model, self.recipe, w, bufs, pool,
                               self.traffic["reference_block"], dtype, precision)

    def control(self, ref: dict) -> dict:
        """The numbers of the control: the reference in bfloat16 at the
        default precision, in the program's place."""
        return compare(self.reference(jnp.bfloat16, jax.lax.Precision.DEFAULT), ref)


def reference_steps(model, recipe, w0, bufs, pool, block, dtype, precision):
    glow = Glow(model, dtype, precision)

    @jax.jit
    def value_grad(w, bufs, x):
        """Mean loss and gradient over ``x``, in blocks of ``block`` rows."""
        xs = x.reshape((-1, min(block, x.shape[0])) + x.shape[1:])

        def body(acc, xb):
            loss, g = jax.value_and_grad(lambda v: glow.nll(v, bufs, xb))(w)
            return jax.tree_util.tree_map(
                lambda a, b: a + b.astype(jnp.float32), acc, (loss, g)), None

        zero = jax.tree_util.tree_map(lambda v: jnp.zeros(v.shape, jnp.float32),
                                      (jnp.zeros(()), w))
        (loss, g), _ = jax.lax.scan(body, zero, xs)
        return jax.tree_util.tree_map(lambda v: v / xs.shape[0], (loss, g))

    step = jax.jit(lambda w, g, mu, nu, s: adamw(w, g, mu, nu, s, recipe),
                   static_argnums=4)
    w = glow.cast(w0)
    mu = jax.tree_util.tree_map(lambda v: jnp.zeros(v.shape, jnp.float32), w0)
    nu = mu
    losses, first = [], None
    for s in range(3):
        loss, grads = value_grad(w, bufs, pool[s % len(pool)])
        losses.append(float(loss))
        w, mu, nu, clipped = step(w, grads, mu, nu, s + 1)
        if first is None:
            first = clipped
    norms = jax.jit(W.step_norms)
    grad = {k: np.asarray(v) for k, v in norms(first).items()}
    change = jax.jit(lambda a, b: W.step_norms(jax.tree_util.tree_map(
        lambda u, v: u.astype(jnp.float32) - v, a, b)))
    update = {k: np.asarray(v) for k, v in change(w, w0).items()}
    return {"loss": losses, "grad": grad, "update": update}


#: leaves whose reference gradient is under this share of the median leaf's
#: move by round-off alone and are left out of the change
STILL_LEAF = 1e-3


def compare(program: dict, ref: dict) -> dict:
    """The numbers compared: the worst gap of the three losses (negative
    log-likelihoods per dimension, which pass through 0 in training: the gap
    is taken against the larger of 1 and the reference's loss), and
    by the worst leaf the gap between the program's and the reference's
    norm of the first gradient and of the change over three steps, against
    the reference's norm of that leaf or of the median leaf if larger."""
    loss_gap = max(abs(p - r) / max(abs(r), 1.0)
                   for p, r in zip(program["loss"], ref["loss"]))
    keys = sorted(ref["grad"])
    g_ref = np.concatenate([ref["grad"][k] for k in keys])
    g_prog = np.concatenate([program["grad"][k] for k in keys])
    g_med = float(np.median(g_ref))
    grad_gap = float(np.max(np.abs(g_prog - g_ref) / np.maximum(g_ref, g_med)))
    u_ref = np.concatenate([ref["update"][k] for k in keys])
    u_prog = np.concatenate([program["update"][k] for k in keys])
    moving = g_ref >= STILL_LEAF * g_med
    u_med = float(np.median(u_ref[moving]))
    update_gap = float(np.max(np.abs(u_prog - u_ref)[moving]
                              / np.maximum(u_ref[moving], u_med)))
    if not all(np.isfinite(program["loss"])):
        loss_gap = float("inf")
    return {"loss_gap": float(loss_gap), "grad_gap": grad_gap,
            "update_gap": update_gap, "still_leaves": int((~moving).sum())}


def work(cell) -> dict:
    """Per step: the model's operations over all chips, and the flow
    kernels' least work on one chip."""
    from bench.work.glow import flow_kernel_work, model_flops_per_example

    t = cell.traffic
    ops, byts = flow_kernel_work(cell.model, t["image_size"], t["batch"] // cell.chips, "train")
    return {"model_flops": 3 * model_flops_per_example(cell.model, t["image_size"]) * t["batch"],
            "kernel_flops": ops, "kernel_bytes": byts}


def checked_run(cell, seed: int, seconds: float, fault=None) -> Run:
    """A run that has made the readings the check compares (set-up's first
    three steps), with the program's state released."""
    run = Run(cell, seed, fault=fault)
    run.setup()
    run.release()
    return run


def calibration_faults(cell) -> list:
    """The faults the calibration plants: the gradient exchange exists only
    across chips; a frozen step reads 1 by the measure and needs no run."""
    return ["half_batch"] + (["no_exchange"] if cell.chips > 1 else [])


def end_to_end(win: dict) -> dict:
    return {"train_samples_per_s": win["samples"] / win["seconds"]}
