"""Fused flow-step megakernel: actnorm → 1x1-conv → affine coupling.

GLOW's whole flow step executes in **one VMEM residency per block** instead
of three kernel launches with HBM round-trips between the sub-layers:

forward (``flowstep_fwd``), given the conditioner output ``h`` (``raw`` on
its first ``ca`` channels, ``t`` on the next ``ca``)::

    x1    = x * exp(an_log_s) + an_b          (actnorm)
    x2    = W^T x1                            (1x1 conv)
    xa,xb = x2[:ca], x2[ca:]
    y     = [xa * exp(clamp*tanh(raw/clamp)) + t ; xb]
    ld[b] += Σ_tile log_s                     (coupling logdet; an/conv logdets
                                               are per-batch constants added by
                                               the caller)

The reversible backward is two kernels around the conditioner VJP, the
unavoidable XLA island (its 3x3 convs belong on the MXU; EXPERIMENTS.md
§Perf/H2):

* ``coupling_half_bwd`` takes ``y``, ``h`` and ``gy`` whole and emits ``x2``
  (``xa`` rebuilt, ``xb`` carried through), ``gh`` (``graw``; ``gt``) for
  the conditioner VJP, and the coupling's part of ``gx2``;
* ``spine_bwd`` adds the conditioner's input cotangent ``gxb`` to the
  untransformed channels of ``gx2``, then walks back through conv+actnorm:
  both intermediates and all cotangents rebuilt, with the (C, C) weight
  gradient and the per-channel actnorm gradients accumulated in VMEM across
  grid steps (TPU grid iteration is sequential, so successive blocks add
  into the same output block).

The coupling's channel split and concatenations happen inside the kernels,
so every operand is a whole-C array and XLA slices nothing.

Layout: channel-major (B, C, M) — batch, channels, flattened spatial.  M
lies on the 128-wide lane axis and the channels on sublanes, so a tile of
GLOW's 12-48 channels is dense (the channel count is padded to a multiple
of 8, not to 128 lanes).  Blocks are (1, C, block_m) with block_m a
multiple of 128 dividing M, or M itself (:func:`lane_tiling`; a large
ragged M is zero-padded); the grid is (B, M // block_m).  Inside a block
the kernels walk the lanes a few vregs at a time, so the kernel body stays
small at any block size.  Per-channel
parameters are (C, 1) columns; the per-batch logdet keeps its lane-dense
(B, 1, 128) accumulator.

Channel mixing (``_mix``) keeps f32 accuracy, which the reversible backward
needs to rebuild every input from the output.  Below ``MXU_CHANNELS`` it
runs as C broadcast multiply-adds on the VPU in plain f32; from there on
the MXU at ``Precision.HIGHEST`` (six bf16 passes) is faster.  The weight
gradient's contraction over the lanes follows the same rule.

Each ``pallas_call`` is named after its wrapper (``name=``): the profiler's
trace and the benchmark find the kernel by that name.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (
    LANES,
    SUBLANES,
    per_batch_shape,
    per_batch_spec,
    pick_block_m,
    resolve_interpret,
)

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
#: elements of one f32 operand block (512 KiB): at most six operands, each
#: double-buffered, stay well inside the 16 MiB of scoped VMEM
BLOCK_ELEMS = 1 << 17
#: elements of one (C, chunk) array in the kernels' inner lane walk (eight
#: vregs; on a v5e four ran 1.5-1.7x slower at C = 12 and 24)
CHUNK_ELEMS = 8 * SUBLANES * LANES
#: channel count from which the channel mix runs on the MXU: on a v5e the
#: VPU's multiply-adds won at C = 12 and 24, the MXU's f32 dot at C = 48
MXU_CHANNELS = 32


def _rows(c: int) -> int:
    return -(-c // SUBLANES) * SUBLANES


def lane_tiling(m: int, c: int, block_m: int | None = None) -> tuple[int, int]:
    """(lane block, padded M) for a (B, c, m) operand.

    The block is ``block_m`` (by default the largest that keeps a block
    within ``BLOCK_ELEMS``) made a multiple of 128 dividing M, or M itself
    when M fits in one block.  A larger M that no multiple of 128 divides is
    zero-padded up to one: padded lanes add nothing to the logdet or the
    gradient sums, and the wrappers slice them off the outputs.
    """
    target = block_m or max(LANES, BLOCK_ELEMS // _rows(c))
    bm = pick_block_m(m, target, align=LANES)
    if bm > target:
        mp = -(-m // LANES) * LANES
        return pick_block_m(mp, target, align=LANES), mp
    return bm, m


def _chunk(block_m: int, c: int) -> int:
    """Lanes per inner iteration: about ``CHUNK_ELEMS`` per (c, chunk) array."""
    if block_m % LANES:
        return block_m
    return pick_block_m(block_m, max(LANES, CHUNK_ELEMS // _rows(c)), align=LANES)


def _walk(block_m: int, chunk: int, body, init):
    """Run ``body(lanes, carry)`` over the block's lanes, ``chunk`` at a time."""
    n = block_m // chunk
    if n == 1:
        return body(slice(None), init)

    def step(j, carry):
        return body(pl.ds(pl.multiple_of(j * chunk, chunk), chunk), carry)

    return jax.lax.fori_loop(0, n, step, init)


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())), precision=HIGHEST,
                               preferred_element_type=F32)


def _mix(a, *parts):
    """``a @ concatenate(parts)`` in f32: on the MXU at ``HIGHEST`` from
    ``MXU_CHANNELS`` channels, else one broadcast multiply-add per input row
    on the VPU."""
    if a.shape[1] >= MXU_CHANNELS:
        return _dot(a, jnp.concatenate(parts) if len(parts) > 1 else parts[0],
                    ((1,), (0,)))
    out, i = None, 0
    for p in parts:
        for r in range(p.shape[0]):
            term = a[:, i:i + 1] * p[r:r + 1]
            out = term if out is None else out + term
            i += 1
    return out


def _fwd_kernel(x_ref, ls_ref, b_ref, wt_ref, h_ref, y_ref, ld_ref,
                *, clamp: float, ca: int, chunk: int):
    scale = jnp.exp(ls_ref[...].astype(F32))         # (C, 1)
    shift = b_ref[...].astype(F32)
    wt = wt_ref[...].astype(F32)                     # W^T, VMEM-resident

    def body(lanes, acc):
        x1 = x_ref[0, :, lanes].astype(F32) * scale + shift
        x2 = _mix(wt, x1)
        h = h_ref[0, :, lanes].astype(F32)
        log_s = clamp * jnp.tanh(h[:ca] / clamp)
        ya = x2[:ca] * jnp.exp(log_s) + h[ca:]
        y_ref[0, :, lanes] = jnp.concatenate([ya, x2[ca:]]).astype(y_ref.dtype)
        return acc + log_s

    acc = _walk(x_ref.shape[2], chunk, body, jnp.zeros((ca, chunk), F32))

    @pl.when(pl.program_id(1) == 0)
    def _init():
        ld_ref[...] = jnp.zeros_like(ld_ref)

    ld_ref[...] += jnp.sum(acc)   # every lane of the (1, 1, LANES) row


def _inv_kernel(y_ref, ls_ref, b_ref, winvt_ref, h_ref, x_ref,
                *, clamp: float, ca: int, chunk: int):
    unscale = jnp.exp(-ls_ref[...].astype(F32))
    shift = b_ref[...].astype(F32)
    winvt = winvt_ref[...].astype(F32)

    def body(lanes, carry):
        y = y_ref[0, :, lanes].astype(F32)
        h = h_ref[0, :, lanes].astype(F32)
        log_s = clamp * jnp.tanh(h[:ca] / clamp)
        xa = (y[:ca] - h[ca:]) * jnp.exp(-log_s)
        x1 = _mix(winvt, xa, y[ca:])
        x_ref[0, :, lanes] = ((x1 - shift) * unscale).astype(x_ref.dtype)
        return carry

    _walk(y_ref.shape[2], chunk, body, 0)


def _coupling_half_bwd_kernel(y_ref, h_ref, gy_ref, gld_ref, x2_ref, gh_ref, gx2_ref,
                              *, clamp: float, ca: int, chunk: int):
    """The coupling half of the reversible backward, from the output side:

        th   = tanh(raw / clamp);  log_s = clamp * th
        xa   = (ya - t) * exp(-log_s)                   (reconstruction)
        gxa  = gya * exp(log_s);   gt = gya
        graw = (gya * xa * exp(log_s) + gld[b]) * (1 - th^2)

    ``x2 = [xa; yb]`` and ``gx2 = [gxa; gyb]`` carry the untransformed
    channels through; the conditioner's part of ``gx2`` joins in ``spine_bwd``.
    """
    gld = gld_ref[pl.program_id(0)]

    def body(lanes, carry):
        y = y_ref[0, :, lanes].astype(F32)
        h = h_ref[0, :, lanes].astype(F32)
        gy = gy_ref[0, :, lanes].astype(F32)
        th = jnp.tanh(h[:ca] / clamp)
        log_s = clamp * th
        e_s = jnp.exp(log_s)
        gya = gy[:ca]
        xa = (y[:ca] - h[ca:]) * jnp.exp(-log_s)
        x2_ref[0, :, lanes] = jnp.concatenate([xa, y[ca:]]).astype(x2_ref.dtype)
        gx2_ref[0, :, lanes] = jnp.concatenate([gya * e_s, gy[ca:]]).astype(gx2_ref.dtype)
        graw = (gya * xa * e_s + gld) * (1.0 - th * th)
        gh_ref[0, :, lanes] = jnp.concatenate([graw, gya]).astype(gh_ref.dtype)
        return carry

    _walk(y_ref.shape[2], chunk, body, 0)


def _fold_lanes(v):
    """Sum the 128-lane groups of ``v`` (rows, k * 128) pairwise into one."""
    groups = [v[:, k:k + LANES] for k in range(0, v.shape[1], LANES)]
    while len(groups) > 1:
        groups = [a + b for a, b in zip(groups[::2], groups[1::2])] + groups[len(groups) & ~1:]
    return groups[0]


def _lane_total(v):
    """(rows, 1): the sum of the 128 lanes of ``v`` as a tree of rotations."""
    shift = LANES // 2
    while shift:
        v = v + pltpu.roll(v, shift, 1)
        shift //= 2
    return v[:, :1]


def _spine_bwd_kernel(x2_ref, gx2_ref, gxb_ref, winvt_ref, w_ref, ls_ref, b_ref,
                      x_ref, gx_ref, gwt_ref, gls_ref, gb_ref, gw_lanes, gw_blk, gw_err,
                      *, ca: int, chunk: int):
    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _init():
        gwt_ref[...] = jnp.zeros_like(gwt_ref)
        gls_ref[...] = jnp.zeros_like(gls_ref)
        gb_ref[...] = jnp.zeros_like(gb_ref)
        gw_err[...] = jnp.zeros_like(gw_err)

    ls = ls_ref[...].astype(F32)
    scale, unscale = jnp.exp(ls), jnp.exp(-ls)
    shift = b_ref[...].astype(F32)
    winvt = winvt_ref[...].astype(F32)
    w = w_ref[...].astype(F32)
    c = w.shape[0]
    # on the VPU, a block of several chunks gathers gW^T per lane and
    # meets across the lanes once, so no long f32 sum runs in one place
    per_lane = c < MXU_CHANNELS and x2_ref.shape[2] > chunk
    gw_blk[...] = jnp.zeros_like(gw_blk)
    if per_lane:
        gw_lanes[...] = jnp.zeros_like(gw_lanes)

    def body(lanes, acc):
        gls, gb = acc
        x2 = x2_ref[0, :, lanes].astype(F32)
        gx2 = gx2_ref[0, :, lanes].astype(F32)
        gx2 = jnp.concatenate([gx2[:ca], gx2[ca:] + gxb_ref[0, :, lanes].astype(F32)])
        x1 = _mix(winvt, x2)                 # conv input, reconstructed
        gx1 = _mix(w, gx2)                   # gx1 = W gx2
        x_ref[0, :, lanes] = ((x1 - shift) * unscale).astype(x_ref.dtype)
        gx_ref[0, :, lanes] = (gx1 * scale).astype(gx_ref.dtype)
        # the block's gW^T += gx2 x1^T, contracted over the lanes
        if c >= MXU_CHANNELS:
            gw_blk[...] += _dot(gx2, x1, ((1,), (1,)))
        elif per_lane:
            for i in range(c):
                gw_lanes[i] += _fold_lanes(gx2 * x1[i:i + 1])
        else:
            for i in range(c):
                gw_blk[:, i:i + 1] = jnp.sum(gx2 * x1[i:i + 1], axis=1, keepdims=True)
        return gls + gx1 * (x1 - shift), gb + gx1

    zeros = jnp.zeros((c, chunk), F32)
    gls, gb = _walk(x2_ref.shape[2], chunk, body, (zeros, zeros))
    gls_ref[...] += jnp.sum(gls, axis=1, keepdims=True)
    gb_ref[...] += jnp.sum(gb, axis=1, keepdims=True)
    if per_lane:
        for i in range(c):
            gw_blk[:, i:i + 1] = _lane_total(gw_lanes[i])
    # Kahan-compensated add of the block's gW: the running sum reaches
    # hundreds, where f32 drops ~1e-5 at each of the B * M / block_m adds
    add = gw_blk[...] - gw_err[...]
    total = gwt_ref[...] + add
    gw_err[...] = (total - gwt_ref[...]) - add
    gwt_ref[...] = total


def _grid(b, c, m, block_m):
    block_m, mp = lane_tiling(m, c, block_m)
    assert mp % block_m == 0, (mp, block_m)
    return (b, mp // block_m), block_m, _chunk(block_m, c), mp


def _pad(mp, *arrays):
    """Each (B, rows, M) array with its lanes zero-padded up to ``mp``."""
    return [a if a.shape[2] == mp else jnp.pad(a, ((0, 0), (0, 0), (0, mp - a.shape[2])))
            for a in arrays]


def _tile(rows, block_m):
    return pl.BlockSpec((1, rows, block_m), lambda i, j: (i, 0, j))


def _whole(*shape):
    """A small operand resident in VMEM for the whole grid."""
    return pl.BlockSpec(shape, lambda i, j: (0,) * len(shape))


def _col(v):
    return v.reshape(-1, 1)


@functools.partial(jax.jit, static_argnames=("clamp", "block_m", "interpret"))
def flowstep_fwd(x, an_log_s, an_b, w, h, *, clamp: float = 2.0,
                 block_m: int | None = None, interpret: bool | None = None):
    """x: (B, C, M); an_*: (C,); w: (C, C); h: (B, 2*ca, M) with ``raw`` on
    its first ``ca`` channels and ``t`` on the rest
    -> (y: (B, C, M), ld_coupling: (B,) f32)."""
    b, c, m = x.shape
    ca = h.shape[1] // 2
    grid, block_m, chunk, mp = _grid(b, c, m, block_m)
    y, ld = pl.pallas_call(
        functools.partial(_fwd_kernel, clamp=clamp, ca=ca, chunk=chunk),
        name="flowstep_fwd",
        grid=grid,
        in_specs=[_tile(c, block_m), _whole(c, 1), _whole(c, 1), _whole(c, c),
                  _tile(2 * ca, block_m)],
        out_specs=[_tile(c, block_m), per_batch_spec()],   # ld[b]: accumulated
        out_shape=[
            jax.ShapeDtypeStruct((b, c, mp), x.dtype),
            per_batch_shape(b),
        ],
        interpret=resolve_interpret(interpret),
    )(*_pad(mp, x), _col(an_log_s), _col(an_b), w.T, *_pad(mp, h))
    return y[..., :m], ld[:, 0, 0]


@functools.partial(jax.jit, static_argnames=("clamp", "block_m", "interpret"))
def flowstep_inv(y, an_log_s, an_b, w_inv, h, *, clamp: float = 2.0,
                 block_m: int | None = None, interpret: bool | None = None):
    """Inverse flow step given ``W^-1`` (computed once outside, O(C^3)):
    y: (B, C, M), h: (B, 2*ca, M) -> x: (B, C, M)."""
    b, c, m = y.shape
    ca = h.shape[1] // 2
    grid, block_m, chunk, mp = _grid(b, c, m, block_m)
    x = pl.pallas_call(
        functools.partial(_inv_kernel, clamp=clamp, ca=ca, chunk=chunk),
        name="flowstep_inv",
        grid=grid,
        in_specs=[_tile(c, block_m), _whole(c, 1), _whole(c, 1), _whole(c, c),
                  _tile(2 * ca, block_m)],
        out_specs=_tile(c, block_m),
        out_shape=jax.ShapeDtypeStruct((b, c, mp), y.dtype),
        interpret=resolve_interpret(interpret),
    )(*_pad(mp, y), _col(an_log_s), _col(an_b), w_inv.T, *_pad(mp, h))
    return x[..., :m]


@functools.partial(jax.jit, static_argnames=("clamp", "block_m", "interpret"))
def coupling_half_bwd(y, h, gy, gld, *, clamp: float = 2.0,
                      block_m: int | None = None, interpret: bool | None = None):
    """Coupling half of the backward (see the kernel): y, gy: (B, C, M),
    h: (B, 2*ca, M), gld: (B,) -> (x2: (B, C, M), gh: like h,
    gx2: (B, C, M) without the conditioner's part)."""
    b, c, m = y.shape
    ca = h.shape[1] // 2
    grid, block_m, chunk, mp = _grid(b, c, m, block_m)
    tile, htile = _tile(c, block_m), _tile(2 * ca, block_m)
    x2, gh, gx2 = pl.pallas_call(
        functools.partial(_coupling_half_bwd_kernel, clamp=clamp, ca=ca, chunk=chunk),
        name="coupling_half_bwd",
        grid=grid,
        in_specs=[tile, htile, tile, pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=[tile, htile, tile],
        out_shape=[
            jax.ShapeDtypeStruct((b, c, mp), y.dtype),
            jax.ShapeDtypeStruct((b, 2 * ca, mp), h.dtype),
            jax.ShapeDtypeStruct((b, c, mp), gy.dtype),
        ],
        interpret=resolve_interpret(interpret),
    )(*_pad(mp, y, h, gy), gld.astype(F32))
    return x2[..., :m], gh[..., :m], gx2[..., :m]


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def spine_bwd(x2, gx2, gxb, w, w_inv, an_log_s, an_b, *, block_m: int | None = None,
              interpret: bool | None = None):
    """Fused conv1x1+actnorm reversible backward (see module docstring).

    x2, gx2: (B, C, M); gxb: (B, C - ca, M), the conditioner's input
    cotangent, added to ``gx2``'s last ``C - ca`` channels -> (x, gx:
    (B, C, M), gw: (C, C) f32, g_log_s, g_b: (C,) f32).
    """
    b, c, m = x2.shape
    ca = c - gxb.shape[1]
    grid, block_m, chunk, mp = _grid(b, c, m, block_m)
    tile = _tile(c, block_m)
    x, gx, gwt, gls, gb = pl.pallas_call(
        functools.partial(_spine_bwd_kernel, ca=ca, chunk=chunk),
        name="spine_bwd",
        grid=grid,
        in_specs=[tile, tile, _tile(c - ca, block_m), _whole(c, c), _whole(c, c),
                  _whole(c, 1), _whole(c, 1)],
        out_specs=[tile, tile, _whole(c, c), _whole(c, 1), _whole(c, 1)],  # trailing 3 accumulated
        out_shape=[
            jax.ShapeDtypeStruct((b, c, mp), x2.dtype),
            jax.ShapeDtypeStruct((b, c, mp), x2.dtype),
            jax.ShapeDtypeStruct((c, c), F32),
            jax.ShapeDtypeStruct((c, 1), F32),
            jax.ShapeDtypeStruct((c, 1), F32),
        ],
        scratch_shapes=[
            # gw_lanes (VPU, several chunks a block), the block's gW^T, its Kahan error
            pltpu.VMEM((c, c, LANES) if c < MXU_CHANNELS and block_m > chunk else (1, 1, 1),
                       F32),
            pltpu.VMEM((c, c), F32),
            pltpu.VMEM((c, c), F32),
        ],
        interpret=resolve_interpret(interpret),
    )(*_pad(mp, x2, gx2, gxb), w_inv.T, w, _col(an_log_s), _col(an_b))
    return x[..., :m], gx[..., :m], gwt.T, gls[:, 0], gb[:, 0]
