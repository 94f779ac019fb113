"""Production meshes.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — device count is locked on
first jax init, and only the dry-run process requests 512 host devices.
"""

from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def auto_mesh_shape(n_devices: int) -> tuple[int, int]:
    """Largest valid ``(data, model)`` factoring of ``n_devices``: the model
    axis takes the largest divisor that is <= sqrt(n) (so data >= model —
    batch sharding is the cheaper collective), data takes the rest.
    256 -> (16, 16); 8 -> (4, 2); 6 -> (3, 2); 4 -> (2, 2); 1 -> (1, 1)."""
    if n_devices < 1:
        raise ValueError(f"n_devices must be positive, got {n_devices}")
    model = 1
    for m in range(1, math.isqrt(n_devices) + 1):
        if n_devices % m == 0:
            model = m
    return (n_devices // model, model)


def make_auto_mesh(shape: tuple[int, ...] | None = None,
                   axes: tuple[str, ...] = ("data", "model")):
    """A ``("data", "model")`` mesh adapted to the *actual* device count.

    With ``shape=None`` the largest valid factoring of ``jax.device_count()``
    is used (see :func:`auto_mesh_shape`) — 1 real device gives a valid
    (1, 1) mesh, a forged-8-CPU host gives (4, 2), a 256-chip pod gives the
    production 16x16.  An explicit ``shape`` must multiply out to the
    device count (``jax.make_mesh`` enforces it).

    The axes are GSPMD (``Auto``) axes: the ``repro.dist`` rules place
    arrays and let the partitioner propagate.  JAX's default ``Explicit``
    axes would type-check every op's sharding instead, and reject e.g. the
    row gather of a model-sharded 1x1-conv weight."""
    if shape is None:
        shape = auto_mesh_shape(jax.device_count())
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def parse_mesh_arg(value: str):
    """Parse a launcher ``--mesh`` value: ``""`` -> no mesh, ``"auto"`` ->
    the auto factoring, ``"d,m"`` -> an explicit (data, model) shape whose
    product must equal the device count."""
    if not value:
        return None
    if value == "auto":
        return make_auto_mesh()
    try:
        shape = tuple(int(t) for t in value.split(","))
    except ValueError:
        shape = ()
    if len(shape) != 2:
        raise ValueError(
            f"--mesh must be 'auto' or 'd,m' (two comma-separated ints whose "
            f"product is the device count), got {value!r}"
        )
    return make_auto_mesh(shape)


def make_test_mesh(n_data: int | None = None, n_model: int | None = None):
    """Small mesh for multi-device subprocess tests — routed through
    :func:`make_auto_mesh`; with no arguments it adapts to whatever device
    count the test process forged."""
    if n_data is None and n_model is None:
        return make_auto_mesh()
    return make_auto_mesh((n_data or 2, n_model or 2))
