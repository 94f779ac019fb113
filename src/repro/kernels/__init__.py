"""Pallas TPU kernels for the performance-critical compute layers.

Each kernel package provides:
  * ``<name>.py`` — the ``pl.pallas_call`` kernel with explicit BlockSpec
    VMEM tiling (TPU is the TARGET; validated with ``interpret=True`` on CPU)
  * ``ops.py``    — the public wrapper: backend-aware dispatch via
    ``kernels.common.kernel_path()`` (compiled Pallas on TPU, with an
    autotuned or a shape-chosen ``block_m``, the fused jnp oracle off-TPU,
    interpret only when forced;
    the coupling/conv1x1/flowstep wrappers carry the full dispatch, the
    attention/ssd/rwkv wrappers resolve the interpret flag per backend)
  * ``ref.py``    — the pure-jnp oracle the kernel is tested against

Kernels:
  * ``flowstep``  — fused GLOW flow-step megakernel, channel-major
    (B, C, M): actnorm + conv1x1 + coupling in one VMEM residency per
    block (fwd), plus the coupling-half and conv/actnorm spine backward
    kernels (§Perf/H2)
  * ``coupling``  — fused affine-coupling transform + logdet (flow hot spot)
  * ``conv1x1``   — invertible 1x1 convolution channel matmul (flow hot spot)
  * ``attention`` — flash attention forward (tiled online softmax, GQA)
  * ``ssd``       — Mamba2 chunked SSD scan with VMEM-resident state
  * ``rwkv``      — RWKV6 wkv recurrence with VMEM-resident state
"""

from repro.kernels.common import kernel_path, resolve_interpret, use_interpret

__all__ = ["kernel_path", "resolve_interpret", "use_interpret"]
