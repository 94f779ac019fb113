"""Operations and bytes of a GLOW step, computed from its shapes.

``model_flops_per_example`` counts the model's multiply-adds (two
operations each) in one forward pass: the conditioner's three convs and
the invertible 1x1 conv of every flow step.  Training does three times
that (forward, and the backward's two products per weight); the reversible
backward's recomputation is not model work and is not counted.

``flow_kernel_work`` is the least work of the flow-step part of a step
(actnorm, 1x1 conv, coupling) that the Pallas kernels carry: the float32
bytes of its (B, M, C) inputs and outputs, read or written once, and its
matmul operations.  It is defined per flow step and direction, not per
kernel call, so that a PR that fuses, splits or re-lays-out the kernels is
read against the same work.
"""

from __future__ import annotations

from bench.lib.weights import scale_shapes


def model_flops_per_example(model: dict, image_size: int) -> float:
    h = model["hidden"]
    total = 0.0
    for side, c in scale_shapes(model, image_size):
        m = side * side
        ca = c // 2
        per_pos = (9 * (c - ca) * h      # conv1, 3x3
                   + h * h               # conv2, 1x1
                   + 9 * h * c           # conv3, 3x3: raw and t
                   + c * c)              # invertible 1x1 conv
        total += 2.0 * per_pos * m * model["k_steps"]
    return total


def flow_kernel_work(model: dict, image_size: int, batch: int, mode: str):
    """``(operations, bytes)`` of the flow steps' kernel work for ``batch``
    examples: ``mode`` is ``"train"`` (forward, then the coupling and spine
    backward) or ``"sample"`` (the inverse)."""
    ops = byts = 0.0
    for side, c in scale_shapes(model, image_size):
        bmc = batch * side * side * c
        if mode == "train":
            # forward: x, raw, t in (2C), y out (C); coupling backward:
            # y_a, raw, t, gy_a in and x_a, gx_a, graw, gt out (4C);
            # spine backward: x2, gx2 in and x, gx out (4C)
            elems = 11 * bmc
            # x @ W forward; x2 @ W^-1, gx2 @ W^T and gW in the backward
            mm = 4 * 2 * bmc * c
        elif mode == "sample":
            elems = 3 * bmc      # y, raw, t in (2C), x out (C)
            mm = 2 * bmc * c     # x2 @ W^-1
        else:
            raise ValueError(mode)
        byts += 4.0 * elems * model["k_steps"]
        ops += float(mm) * model["k_steps"]
    return ops, byts
