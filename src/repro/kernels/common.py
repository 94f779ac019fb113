"""Shared kernel utilities + the backend-aware kernel configuration layer.

Every Pallas wrapper in ``repro.kernels`` routes its execution decision
through this module instead of hardcoding ``interpret=True``:

* ``kernel_path()`` — how the *flow hot-path* wrappers (coupling, conv1x1,
  flowstep) should execute:

  - ``"compiled"``  on TPU: real ``pallas_call`` lowering (the perf path;
    see ``COMPILED_BACKENDS`` for why GPU is excluded for now).
  - ``"reference"`` on CPU: the pure-jnp oracle, XLA-compiled.  Interpret-mode
    Pallas executes the kernel body per grid step in emulation — it is a
    *debugging* mode, not a perf path, and on CPU the jnp oracle is the same
    math fused by XLA.  This is the fix for the silent-slow default that made
    ``grad_mode="coupled"`` lose to plain autodiff (EXPERIMENTS.md §Perf/H2).
  - ``"interpret"``  when forced: kernel bodies run under the Pallas
    interpreter (kernel-correctness tests, CI smoke).

  Override with ``REPRO_PALLAS_INTERPRET=1`` (force interpret) or ``=0``
  (force compiled, even on CPU — will fail without a Pallas lowering).
  Inside ``with reference_kernels():`` the path is ``"reference"`` on any
  backend: the float32 oracle a chip run is checked against.

* ``resolve_interpret(interpret)`` — maps the ``interpret=None`` default of
  the kernel entry points onto the same policy (compiled off-CPU, interpret
  as the CPU fallback).

The resolution is logged once per distinct outcome (a one-line breadcrumb so
a slow run is never silently in emulation).

Autotuning: ``tuned_block_m`` measures a small candidate set of legal
``block_m`` tilings and persists the winner in a JSON cache keyed by
``(op, backend, device_kind, shape, dtype)`` so repeat runs skip tuning
entirely.  On the interpret/reference paths (where timing the emulation is
meaningless) it falls back to the deterministic divisor pick.
"""

from __future__ import annotations

import contextlib
import contextvars
import json
import logging
import os
import time
from typing import Callable, Iterable, Optional, Sequence

import jax

from repro.utils.cache import CHECKOUT_ROOT

_log = logging.getLogger("repro.kernels")

#: backends whose Pallas lowering these kernels actually support.  TPU only:
#: every kernel in this repo accumulates into revisited output blocks
#: (logdet, gW, per-channel actnorm grads), which is only correct because
#: the TPU grid iterates *sequentially* — on GPU (Triton) grid programs run
#: in parallel and the same pattern is a data race, and several kernels use
#: TPU-specific scratch shapes.  Widen this only together with a GPU kernel
#: story; until then GPU hosts take the reference path like CPU.
COMPILED_BACKENDS = ("tpu",)

INTERPRET_ENV = "REPRO_PALLAS_INTERPRET"

_logged_keys: set = set()

_scoped_path: contextvars.ContextVar[Optional[str]] = contextvars.ContextVar(
    "repro_kernel_path", default=None
)


@contextlib.contextmanager
def reference_kernels():
    """Trace the flow hot path through the jnp oracles on any backend.

    For parity checks on the chip: jit a *separate* function inside the
    block, since a trace made here keeps the oracle path for its lifetime.
    """
    token = _scoped_path.set("reference")
    try:
        yield
    finally:
        _scoped_path.reset(token)


def _env_interpret() -> Optional[bool]:
    raw = os.environ.get(INTERPRET_ENV)
    if raw is None:
        return None
    return raw.strip().lower() in ("1", "true", "yes", "interpret")


def kernel_path() -> str:
    """Execution path for the flow hot-path wrappers.

    ``"compiled"`` | ``"reference"`` | ``"interpret"`` — see module docstring.
    Read per call (cheap), logged once per distinct resolution.
    """
    if _scoped_path.get() is not None:
        return _scoped_path.get()
    backend = jax.default_backend()
    forced = _env_interpret()
    if forced is True:
        path, why = "interpret", f"{INTERPRET_ENV}=1"
    elif forced is False:
        path, why = "compiled", f"{INTERPRET_ENV}=0"
    elif backend in COMPILED_BACKENDS:
        path, why = "compiled", f"backend={backend}"
    else:
        path, why = "reference", f"backend={backend} (jnp oracle; interpret is debug-only)"
    _log_once(("path", path, why), "pallas kernel path: %s (%s)", path, why)
    return path


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Resolve an ``interpret=None`` default for a raw ``pallas_call`` entry
    point: compiled on TPU, interpret as the off-TPU fallback; the
    ``REPRO_PALLAS_INTERPRET`` override wins either way."""
    if interpret is not None:
        return interpret
    forced = _env_interpret()
    if forced is not None:
        resolved = forced
        why = f"{INTERPRET_ENV}={int(forced)}"
    else:
        resolved = jax.default_backend() not in COMPILED_BACKENDS
        why = f"backend={jax.default_backend()}"
    _log_once(
        ("interpret", resolved, why), "pallas interpret=%s (%s)", resolved, why
    )
    return resolved


def _log_once(key, fmt, *args):
    if key not in _logged_keys:
        _logged_keys.add(key)
        _log.info(fmt, *args)


def reset_kernel_config():
    """Forget the log-once state and the in-memory autotune cache (tests)."""
    global _tune_cache
    _logged_keys.clear()
    _tune_cache = None


def use_interpret() -> bool:
    """Back-compat alias: the resolved interpret flag for a raw pallas call."""
    return resolve_interpret(None)


#: lane width of a TPU vector register: the last dim of a VMEM block must be
#: a multiple of it, or the array's full extent
LANES = 128
#: sublane count: the second-to-last dim of a VMEM block must be a multiple
#: of it, or the array's full extent
SUBLANES = 8


def per_batch_shape(b: int):
    """Shape of a per-batch scalar accumulator output (e.g. a logdet): one
    lane-dense ``(1, LANES)`` row per batch element, every lane holding the
    same value, so its block obeys the TPU tiling rule.  Read it as
    ``out[:, 0, 0]``."""
    return jax.ShapeDtypeStruct((b, 1, LANES), jax.numpy.float32)


def per_batch_spec():
    """Block of :func:`per_batch_shape` for a ``(B, M // block_m)`` grid: row
    ``b`` is revisited by every ``m`` step, which accumulate into it."""
    from jax.experimental import pallas as pl

    return pl.BlockSpec((1, 1, LANES), lambda i, j: (i, 0, 0))


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def spatial_size(shape) -> int:
    """Flattened spatial extent M of a (B, ..., C) array — the middle axes
    the coupling/conv1x1 wrappers collapse into the kernels' (B, M, C) view."""
    m = 1
    for d in shape[1:-1]:
        m *= d
    return max(m, 1)


def flatten_bmc(v):
    """Collapse a (B, ..., C) array to the kernels' (B, M, C) layout."""
    return v.reshape(v.shape[0], spatial_size(v.shape), v.shape[-1])


def block_m_for(v, target: int = 256) -> int:
    """Legal block_m for a (B, ..., C) array's flattened spatial axis."""
    return pick_block_m(spatial_size(v.shape), target)


def pick_block_m(m: int, target: int = 256, align: int = SUBLANES) -> int:
    """Largest multiple of ``align`` that divides ``m`` and is <= ``target``
    (or is ``align``, for a smaller target); ``m`` itself when ``m <=
    target`` or no such divisor exists.

    The kernels tile the flattened spatial axis in blocks that must divide
    ``m`` exactly.  The TPU compiler accepts a block's second-to-last dim
    only when it is a multiple of ``SUBLANES`` or the whole axis (the
    (B, M, C) kernels, the default), and its last dim only when it is a
    multiple of ``LANES`` or the whole axis (the channel-major (B, C, M)
    kernels: ``align=LANES``).  So a ragged ``m`` (e.g. 300, which no
    multiple of 8 divides) runs as one block.
    """
    if m <= 0:
        raise ValueError(f"m must be positive, got {m}")
    if m <= target:
        return m
    for b in range(max(target - target % align, align), 0, -align):
        if m % b == 0:
            return b
    return m


# ---------------------------------------------------------------------------
# block_m autotuner (measured, persistently cached)
# ---------------------------------------------------------------------------

#: full-path override for the persistent cache file (wins over the dir env)
AUTOTUNE_CACHE_ENV = "REPRO_AUTOTUNE_CACHE"
#: directory override: parallel CI jobs / subprocess tests point this at a
#: private directory so concurrent runs never race on one shared JSON file
TUNE_CACHE_DIR_ENV = "REPRO_TUNE_CACHE_DIR"
_CACHE_BASENAME = "block_m.json"
_DEFAULT_CACHE = str(CHECKOUT_ROOT / "artifacts" / "autotune" / _CACHE_BASENAME)
#: tiling targets swept by the tuner; each maps to a *legal* divisor of M
DEFAULT_BLOCK_TARGETS = (64, 128, 256, 512, 1024)

_tune_cache: Optional[dict] = None


def _cache_path() -> str:
    explicit = os.environ.get(AUTOTUNE_CACHE_ENV)
    if explicit:
        return explicit
    cache_dir = os.environ.get(TUNE_CACHE_DIR_ENV)
    if cache_dir:
        return os.path.join(cache_dir, _CACHE_BASENAME)
    return _DEFAULT_CACHE


def _load_tune_cache() -> dict:
    global _tune_cache
    if _tune_cache is None:
        try:
            with open(_cache_path()) as f:
                _tune_cache = json.load(f)
        except (OSError, ValueError):
            _tune_cache = {}
    return _tune_cache


def _save_tune_cache():
    path = _cache_path()
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            json.dump(_tune_cache, f, indent=1, sort_keys=True)
    except OSError:  # read-only FS: the in-memory cache still amortizes
        pass


def candidate_block_ms(
    m: int, targets: Sequence[int] = DEFAULT_BLOCK_TARGETS
) -> list[int]:
    """Distinct legal block_m candidates (each divides ``m`` and is a
    multiple of ``SUBLANES`` or ``m`` itself)."""
    return sorted({pick_block_m(m, t) for t in targets})


def time_candidate(fn: Callable[[], object], warmup: int = 1, iters: int = 3) -> float:
    """Median wall seconds of ``fn()`` after warmup (compile excluded)."""
    for _ in range(warmup):
        jax.block_until_ready(fn())
    samples = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        samples.append(time.perf_counter() - t0)
    samples.sort()
    return samples[len(samples) // 2]


def _tune_key(op: str, shape, dtype) -> str:
    # device_kind: a block_m measured on one chip generation is not served
    # to another
    return "|".join(
        (op, jax.default_backend(), jax.devices()[0].device_kind,
         "x".join(map(str, shape)), str(jax.numpy.dtype(dtype)))
    )


def tuned_block_m(
    op: str,
    shape: Iterable[int],
    dtype,
    measure: Optional[Callable[[int], float]] = None,
    targets: Sequence[int] = DEFAULT_BLOCK_TARGETS,
) -> int:
    """Best measured ``block_m`` for one (op, shape, dtype, device) site.

    ``measure(block_m) -> seconds`` runs the compiled kernel at one candidate
    tiling; the winner is persisted (``artifacts/autotune/block_m.json`` in
    the checkout by default; ``REPRO_TUNE_CACHE_DIR`` relocates the directory — one private
    dir per parallel CI job / subprocess test — and ``REPRO_AUTOTUNE_CACHE``
    pins the full path) so every later process skips straight to the cached
    choice.  Without a ``measure`` callable —
    or on the interpret/reference paths, where timing the emulation is noise —
    the deterministic ``pick_block_m`` divisor is returned.

    Measurement needs *concrete* arrays, so under ``jit`` tracing the ops
    layer calls this with ``measure=None`` and the persisted cache is the
    only source of a tuned choice: tune by invoking the wrapper eagerly once
    per shape (``kernels_bench`` does; so does any eager warmup call) and
    every traced call thereafter — in this process or a later one — reads
    the cached winner.
    """
    shape = tuple(int(d) for d in shape)
    m = spatial_size(shape)
    if kernel_path() != "compiled":
        return pick_block_m(m)
    cands = candidate_block_ms(m, targets)
    if len(cands) == 1:
        return cands[0]
    key = _tune_key(op, shape, dtype)
    cache = _load_tune_cache()
    if key in cache and cache[key] in cands:
        return int(cache[key])
    if measure is None:  # tracing / no way to measure: deterministic pick
        return pick_block_m(m)
    timings = {bm: measure(bm) for bm in cands}
    best = min(timings, key=timings.get)
    cache[key] = int(best)
    _save_tune_cache()
    _log.info(
        "autotuned %s: block_m=%d out of %s (%.1fus best)",
        key, best, cands, timings[best] * 1e6,
    )
    return int(best)


def resolve_block_m(op: str, x, block_m: Optional[int], measure=None) -> int:
    """Ops-layer entry: explicit ``block_m`` is made legal for the shape;
    ``None`` consults the autotuner — measuring on eager concrete-array
    calls, cache-lookup-only under tracing (see :func:`tuned_block_m`)."""
    m = spatial_size(x.shape)
    if block_m is not None:
        return pick_block_m(m, block_m)
    if isinstance(x, jax.core.Tracer):
        measure = None
    return tuned_block_m(op, x.shape, x.dtype, measure)
