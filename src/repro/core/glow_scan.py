"""Scan-compiled GLOW: homogeneous flow-step stacks driven by ``lax.scan``.

``build_glow`` unrolls ``n_scales * k_steps * 3`` layers into Python — HLO
size and XLA compile time grow linearly with depth.  ``GlowStepStack``
instead stacks the parameters of one scale's ``k`` identical flow steps
(actnorm → LU-parameterized 1x1 conv → affine coupling) along a leading
layer axis and drives them with the scan engine: **one** traced step body
per scale, so trace/compile cost is O(1) in ``k_steps``.

The step body is the fused flow-step megakernel path
(``repro.kernels.flowstep``): the forward is a single fused launch given the
conditioner's raw/t, and the ``grad_mode="coupled"`` backward is the
two fused kernels (coupling backward, conv+actnorm spine backward)
sandwiching the conditioner VJP — the only XLA island (EXPERIMENTS.md
§Perf/H2).  The stack is itself an ``Invertible`` with a ``fused_bwd`` hook
(via the shared :func:`repro.core.autodiff.scan_backward`), so it composes
inside the multiscale ``InvertibleChain`` exactly like the unrolled steps
while keeping both properties: O(1)-in-depth HLO *and* the megakernel
backward.

The stack takes and returns (B, H, W, C), but its scans carry the kernels'
channel-major (B, C, M) layout (M = H * W on the TPU's lanes): one layout
change on entry and one on exit, and per step only the conditioner's input
half and its output change layout, since the conditioner runs NHWC.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.scipy.linalg import solve_triangular

from repro.core.actnorm import ActNorm
from repro.core.autodiff import make_scan_apply, scan_backward
from repro.core.chain import InvertibleChain, OnFirst, Pack, Split
from repro.core.conv1x1 import Conv1x1
from repro.core.haar import HaarSqueeze, Squeeze
from repro.core.types import Invertible, float0_like
from repro.kernels.flowstep.ref import channel_mix
from repro.nn.nets import CouplingCNN


def _mm(a, b):
    """f32-exact matmul: the step must invert to f32 accuracy (the reversible
    backward rebuilds every input from the output), so its channel mixing
    never takes the chip's one-pass bf16 default."""
    return jnp.matmul(a, b, precision=lax.Precision.HIGHEST)


def _to_channel_major(v):
    """(B, ..., C) -> the flow kernels' (B, C, M)."""
    return v.reshape(v.shape[0], -1, v.shape[-1]).transpose(0, 2, 1)


def _from_channel_major(v, spatial):
    """(B, C, M) -> (B, *spatial, C)."""
    return v.transpose(0, 2, 1).reshape(v.shape[0], *spatial, v.shape[1])


def _stack_trees(trees):
    return jax.tree_util.tree_map(lambda *leaves: jnp.stack(leaves), *trees)


def resolve_coupled_bwd(choice: str | None = None) -> str:
    """Backend-resolved backward strategy for ``grad_mode="coupled"``.

    * ``"reversible"`` — output-only residuals + the fused megakernel reverse
      scan: O(1) activation residency.  The winning strategy where memory is
      the binding constraint (accelerator HBM — the paper's regime).
    * ``"stored"`` — the same fused forward graph differentiated by XLA's
      stored-activation transpose.  On CPU (host RAM abundant, compute
      binding) the reversible walk pays an extra conditioner primal
      (~4/3 backward compute) it can never earn back, so the fast path there
      is to *not* pay the reversibility tax (EXPERIMENTS.md §Perf/H2).

    ``REPRO_COUPLED_BWD`` overrides; ``"auto"``/None resolves per backend.
    """
    import os

    from repro.kernels.common import COMPILED_BACKENDS

    env = os.environ.get("REPRO_COUPLED_BWD")
    choice = env or choice or "auto"
    if choice not in ("auto", "reversible", "stored"):
        raise ValueError(f"coupled_bwd must be auto|reversible|stored, got {choice}")
    if choice != "auto":
        return choice
    return "reversible" if jax.default_backend() in COMPILED_BACKENDS else "stored"


def default_scan_unroll(k_steps: int) -> int:
    """Backend-aware scan unroll factor (``REPRO_SCAN_UNROLL`` overrides).

    On CPU the XLA backend compiles conv/conv-VJP ops inside while-loop
    bodies to a markedly slower path (~3x in our microbenches), so the scan
    is fully unrolled at *lowering* time — tracing still happens once, and
    compile stays cheaper than the Python-unrolled chain.  On TPU loops
    lower well and ``unroll=1`` keeps HLO size O(1) in depth.
    """
    import os

    env = os.environ.get("REPRO_SCAN_UNROLL")
    if env:
        return max(1, min(int(env), k_steps))
    from repro.kernels.common import COMPILED_BACKENDS

    return 1 if jax.default_backend() in COMPILED_BACKENDS else k_steps


class GlowStepStack(Invertible):
    """``k_steps`` homogeneous GLOW flow steps with layer-stacked params.

    Operates on a (B, H, W, C) array (wrap in ``OnFirst`` for the multiscale
    tuple state).  ``grad_mode`` shapes the *internal* scan engine used by
    :meth:`forward` (``"coupled"`` wires the megakernel ``step_bwd`` into
    ``make_scan_apply``); the :meth:`fused_bwd` hook — what an outer coupled
    chain dispatches — always runs the fused reverse scan and is
    mode-independent, like every other layer's hook.
    """

    def __init__(self, k_steps: int, hidden: int = 64, clamp: float = 2.0,
                 grad_mode: str = "invertible", conditioner_factory=None,
                 unroll: int | None = None, coupled_bwd: str = "auto",
                 psum_axis: str | None = None):
        self.k_steps = k_steps
        self.hidden = hidden
        self.clamp = clamp
        self.grad_mode = grad_mode
        self.coupled_bwd = (
            resolve_coupled_bwd(coupled_bwd) if grad_mode == "coupled" else None
        )
        self.unroll = default_scan_unroll(k_steps) if unroll is None else unroll
        self._factory = conditioner_factory or (
            lambda c_out: CouplingCNN(c_out, hidden=hidden)
        )
        # "coupled" + stored strategy: same fused forward, gradients by XLA's
        # stored-activation transpose — the scan engine sees plain autodiff
        self._apply_mode = (
            "autodiff" if self.coupled_bwd == "stored" else grad_mode
        )
        # record the *effective* reduction axis: only the custom-VJP modes
        # psum cotangents in their backward (repro.dist.flow consults this)
        self.psum_axis = (
            psum_axis if self._apply_mode in ("invertible", "coupled") else None
        )
        self._scan_psum_axis = psum_axis

    # -- parameters ---------------------------------------------------------

    def init(self, rng, x, d_cond: int = 0):
        c = x.shape[-1]
        ca = c // 2
        if ca < 1:
            raise ValueError(f"GlowStepStack needs >= 2 channels, got {c}")
        an, conv = ActNorm(), Conv1x1()
        steps = []
        for k in jax.random.split(rng, self.k_steps):
            k_conv, k_net = jax.random.split(k)
            net = self._factory(2 * ca)
            steps.append({
                "an": an.init(k, x),
                "lu": conv.init(k_conv, x),
                "net": net.init(k_net, c - ca, d_cond),
            })
        return _stack_trees(steps)

    # -- per-step pieces ----------------------------------------------------

    def _lu_full(self, lu):
        c = lu["l"].shape[-1]
        dt = lu["l"].dtype
        eye = jnp.eye(c, dtype=dt)
        l_full = jnp.tril(lu["l"], -1) + eye
        u_full = jnp.triu(lu["u"], 1) + jnp.diag(
            lu["sign_s"].astype(dt) * jnp.exp(lu["log_s"])
        )
        return l_full, u_full

    def _w(self, lu):
        l_full, u_full = self._lu_full(lu)
        return _mm(l_full, u_full)[lu["inv_perm"]]

    def _w_inv(self, lu):
        l_full, u_full = self._lu_full(lu)
        return self._w_inv_from(l_full, u_full, lu["inv_perm"])

    @staticmethod
    def _w_inv_from(l_full, u_full, inv_perm):
        eye = jnp.eye(l_full.shape[0], dtype=l_full.dtype)
        b = solve_triangular(
            u_full, solve_triangular(l_full, eye, lower=True), lower=False
        )
        return b[:, inv_perm]

    def _net_out(self, net_params, xb, cond):
        net = self._factory(0)  # d_out unused at apply time
        with jax.named_scope("conditioner"):  # its VJP: transpose(jvp(conditioner))
            return net.apply(net_params, xb, cond)

    def _net_cm(self, net_params, xb, cond, spatial):
        """The conditioner on a channel-major half: (B, C - ca, M) in, its
        (B, 2 * ca, M) output back; the conditioner itself runs NHWC."""
        with jax.named_scope("kernel_layout"):
            xb = _from_channel_major(xb, spatial)
        h = self._net_out(net_params, xb, cond)
        with jax.named_scope("kernel_layout"):
            return _to_channel_major(h)

    def _ld_const(self, p, m):
        """Per-batch-constant logdet: actnorm + conv1x1 (spatial * Σ log_s)."""
        return m * (
            jnp.sum(p["an"]["log_s"]) + jnp.sum(p["lu"]["log_s"])
        ).astype(jnp.float32)

    # The step functions take and return the kernels' channel-major
    # (B, C, M) layout: the scans carry it, and only the conditioner's
    # input and output change layout per step.

    def _step_fwd(self, p, x, cond, spatial):
        from repro.kernels.common import kernel_path
        from repro.kernels.flowstep.ops import fused_flowstep_fwd

        ca = x.shape[1] // 2
        an_ls, an_b = p["an"]["log_s"], p["an"]["b"]
        with jax.named_scope("conv1x1"):
            w = self._w(p["lu"]).astype(jnp.float32)
        with jax.named_scope("actnorm"):
            xn = x.astype(jnp.float32) * jnp.exp(an_ls)[:, None] + an_b[:, None]
        if kernel_path() == "reference":
            # fused-XLA step: compute the conv output once, slice the
            # conditioner input out of it — no duplicated half-matmul
            with jax.named_scope("conv1x1"):
                x2 = channel_mix(w, xn)
            h = self._net_cm(p["net"], x2[:, ca:].astype(x.dtype), cond, spatial)
            log_s = self.clamp * jnp.tanh(h[:, :ca].astype(jnp.float32) / self.clamp)
            ya = x2[:, :ca] * jnp.exp(log_s) + h[:, ca:].astype(jnp.float32)
            y = jnp.concatenate([ya, x2[:, ca:]], axis=1).astype(x.dtype)
            ld_c = jnp.sum(log_s, axis=(1, 2))
            return y, ld_c + self._ld_const(p, x.shape[2])
        # megakernel path: the conditioner input is the untransformed half
        # after actnorm+conv, via the half-matmul — the step proper stays a
        # single fused launch
        with jax.named_scope("conv1x1"):
            xb = channel_mix(w[:, ca:], xn)
        h = self._net_cm(p["net"], xb.astype(x.dtype), cond, spatial)
        y, ld_c = fused_flowstep_fwd(x, an_ls, an_b, w, h, clamp=self.clamp)
        return y, ld_c + self._ld_const(p, x.shape[2])

    def _step_inv(self, p, y, cond, spatial):
        from repro.kernels.flowstep.ops import fused_flowstep_inv

        ca = y.shape[1] // 2
        with jax.named_scope("kernel_layout"):
            yb = y[:, ca:]
        h = self._net_cm(p["net"], yb, cond, spatial)
        with jax.named_scope("conv1x1"):
            w_inv = self._w_inv(p["lu"]).astype(jnp.float32)
        return fused_flowstep_inv(
            y, p["an"]["log_s"], p["an"]["b"], w_inv, h, clamp=self.clamp,
        )

    def _step_bwd(self, p, y, gy, gld, cond, spatial):
        """Megakernel reversible backward for one flow step.

        Stage 1 (fused coupling kernel) reconstructs the conv output and
        emits ``gh``; the conditioner VJP (XLA) maps it onto its params and
        input; stage 2 (fused spine kernel) adds that input cotangent and
        walks back through conv1x1 + actnorm — reconstruction and all
        cotangents, one VMEM pass each side.
        """
        from repro.kernels.flowstep.ops import (
            fused_coupling_half_bwd,
            fused_spine_bwd,
        )

        ca = y.shape[1] // 2
        an_ls, an_b = p["an"]["log_s"], p["an"]["b"]
        lu = p["lu"]
        with jax.named_scope("conv1x1"):
            l_full, u_full = self._lu_full(lu)  # shared by W, W^-1 and the LU pullback
            w = _mm(l_full, u_full)[lu["inv_perm"]].astype(jnp.float32)
            w_inv = self._w_inv_from(l_full, u_full, lu["inv_perm"]).astype(jnp.float32)

        with jax.named_scope("kernel_layout"):
            yb = lax.stop_gradient(y[:, ca:])
        h, net_vjp = jax.vjp(
            lambda np_, xb_, c_: self._net_cm(np_, xb_, c_, spatial), p["net"], yb, cond
        )
        # stage 1: fused coupling backward (one VMEM pass)
        x2, gh, gx2 = fused_coupling_half_bwd(y, h, gy, gld, clamp=self.clamp)
        g_net, gxb_net, gcond = net_vjp(gh)
        # stage 2: fused conv+actnorm spine backward (one VMEM pass)
        x, gx, gw, g_an_ls, g_an_b = fused_spine_bwd(
            x2, gx2, gxb_net, w, w_inv, an_ls, an_b
        )
        x = lax.stop_gradient(x)

        # logdet cotangents: per-batch constants land on the log-scales
        s_gld = y.shape[2] * jnp.sum(gld.astype(jnp.float32))
        # LU chain rule: W = (L @ U)[inv_perm]  =>  gA[inv_perm] = gW
        with jax.named_scope("conv1x1"):
            ga = jnp.zeros_like(gw).at[lu["inv_perm"]].set(gw).astype(l_full.dtype)
            gl_full = _mm(ga, u_full.T)
            gu_full = _mm(l_full.T, ga)
            sign = lu["sign_s"].astype(lu["log_s"].dtype)
            g_lu_ls = (
                jnp.diagonal(gu_full).astype(lu["log_s"].dtype)
                * sign * jnp.exp(lu["log_s"])
                + s_gld.astype(lu["log_s"].dtype)
            )
        gp = {
            "an": {
                "log_s": (g_an_ls + s_gld).astype(an_ls.dtype),
                "b": g_an_b.astype(an_b.dtype),
            },
            "lu": {
                "inv_perm": jnp.zeros_like(lu["inv_perm"]),  # float0 after scan
                "l": jnp.tril(gl_full, -1).astype(lu["l"].dtype),
                "u": jnp.triu(gu_full, 1).astype(lu["u"].dtype),
                "sign_s": jnp.zeros_like(lu["sign_s"]),      # float0 after scan
                "log_s": g_lu_ls,
            },
            "net": g_net,
        }
        return x, gx, gp, gcond

    # -- Invertible surface -------------------------------------------------
    # (B, ..., C) in and out; the scans run channel-major, with one layout
    # change on entry and one on exit.

    def _scan_apply(self, spatial):
        step_bwd = (
            (lambda p, y, gy, gld, extra, i:
             self._step_bwd(p, y, gy, gld, extra, spatial))
            if self._apply_mode == "coupled"
            else None
        )
        return make_scan_apply(
            lambda p, x, extra, i: self._step_fwd(p, x, extra, spatial),
            lambda p, y, extra, i: self._step_inv(p, y, extra, spatial),
            grad_mode=self._apply_mode,
            step_bwd=step_bwd,
            unroll=self.unroll,
            psum_axis=self._scan_psum_axis,
        )

    def forward(self, params, x, cond=None):
        spatial = x.shape[1:-1]
        with jax.named_scope("kernel_layout"):
            xc = _to_channel_major(x)
        y, ld = self._scan_apply(spatial)(params, xc, cond)
        with jax.named_scope("kernel_layout"):
            return _from_channel_major(y, spatial), ld

    def inverse(self, params, y, cond=None):
        spatial = y.shape[1:-1]
        n = jax.tree_util.tree_leaves(params)[0].shape[0]
        ids = jnp.arange(n, dtype=jnp.int32)

        def body(yc, sp):
            p, _i = sp
            return self._step_inv(p, yc, cond, spatial), None

        with jax.named_scope("kernel_layout"):
            yc = _to_channel_major(y)
        x, _ = lax.scan(body, yc, (params, ids), reverse=True, unroll=self.unroll)
        with jax.named_scope("kernel_layout"):
            return _from_channel_major(x, spatial)

    # -- grad_mode="coupled" hook ------------------------------------------
    def fused_bwd(self, params, y, gy, gld, cond=None):
        """Fused reversible backward for the whole stack: one reverse
        ``lax.scan`` of the megakernel step backward (O(1) HLO in depth)."""
        spatial = y.shape[1:-1]
        with jax.named_scope("kernel_layout"):
            yc, gyc = _to_channel_major(y), _to_channel_major(gy)
        x, gx, gstacked, gcond = scan_backward(
            lambda p, y_, gy_, gld_, extra, i:
                self._step_bwd(p, y_, gy_, gld_, extra, spatial),
            params, yc, gyc, gld, cond, unroll=self.unroll,
        )
        with jax.named_scope("kernel_layout"):
            x, gx = _from_channel_major(x, spatial), _from_channel_major(gx, spatial)
        # integer buffers carry float0 cotangents (scan stacked int zeros)
        for name in ("inv_perm", "sign_s"):
            gstacked["lu"][name] = float0_like(params["lu"][name])
        return x, gx, gstacked, gcond


def build_glow_scanned(
    n_scales: int = 3,
    k_steps: int = 8,
    hidden: int = 64,
    grad_mode: str = "invertible",
    haar: bool = True,
    clamp: float = 2.0,
    coupled_bwd: str = "auto",
    unroll: int | None = None,
    psum_axis: str | None = None,
) -> InvertibleChain:
    """Scan-compiled GLOW for (B, H, W, C) inputs (H, W divisible by
    2**n_scales): per scale, squeeze → one :class:`GlowStepStack` of
    ``k_steps`` fused flow steps → split.  Same density model as
    :func:`repro.core.glow.build_glow`; trace cost O(1) in ``k_steps`` and
    the training path routes through the flow-step megakernel (compiled
    Pallas off-CPU, fused XLA reference on CPU).

    ``coupled_bwd`` picks the ``grad_mode="coupled"`` backward strategy
    (see :func:`resolve_coupled_bwd`): ``"auto"`` resolves per backend —
    the reversible megakernel reverse scan off-CPU, XLA's stored-activation
    transpose on CPU.  With the stored strategy the *whole* chain
    differentiates by plain AD (the output-residual chain VJP would discard
    the stored activations at its boundary).

    ``psum_axis`` makes the chain's custom VJP data-parallel-safe under
    ``shard_map`` over the named mesh axis (``repro.dist.flow``): parameter
    and cond cotangents are psum-reduced at the VJP boundary.  With the CPU
    "stored" strategy the chain differentiates by plain AD and the dist
    helpers reduce the gradients themselves (``InvertibleChain.psum_axis``
    reads back the effective setting)."""
    squeeze = HaarSqueeze if haar else Squeeze
    chain_mode = grad_mode
    if grad_mode == "coupled" and resolve_coupled_bwd(coupled_bwd) == "stored":
        chain_mode = "autodiff"
    layers = [Pack()]
    for scale in range(n_scales):
        layers.append(OnFirst(squeeze()))
        # psum_axis goes on the *outermost* chain only: the chain VJP reduces
        # every layer's cotangents once; a stack-level psum would double-
        # reduce on the generic invert-then-vjp path (which differentiates
        # through the stack's own custom VJP)
        layers.append(
            OnFirst(GlowStepStack(k_steps, hidden=hidden, clamp=clamp,
                                  grad_mode=grad_mode, coupled_bwd=coupled_bwd,
                                  unroll=unroll))
        )
        if scale != n_scales - 1:
            layers.append(Split())
    return InvertibleChain(layers, grad_mode=chain_mode, psum_axis=psum_axis)
