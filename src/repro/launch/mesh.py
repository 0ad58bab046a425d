"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never touches
jax device state (device count is locked at first jax init, and only
dryrun.py sets the 512-host-device XLA flag).

Meshes use Auto axis types: ``jax.make_mesh`` defaults to Explicit ones,
and the code here is written for shardings that the compiler propagates.
"""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_tiny_mesh", "make_window_mesh"]


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_window_mesh(devices=None, *, axis: str = "data"):
    """1-D data-parallel mesh for window sharding (the executor's sharded
    dispatch path).

    ``devices`` is an int (the first N of ``jax.devices()``), an explicit
    device sequence, or None for every device.  The axis is named "data" so
    ``distributed.sharding.Sharder`` / ``batch_partition_axes`` resolve it as
    data-parallel.  Prefix meshes (N < device count) are built from the
    device list directly, in ``jax.devices()`` order.
    """
    import numpy as np

    avail = jax.devices()
    if devices is None:
        devs = avail
    elif isinstance(devices, int):
        if not 1 <= devices <= len(avail):
            raise ValueError(
                f"devices={devices} outside [1, {len(avail)}] available")
        devs = avail[:devices]
    else:
        devs = list(devices)
        if not devs:
            raise ValueError("empty device sequence")
    if devs == avail:
        return _auto_mesh((len(devs),), (axis,))
    return jax.sharding.Mesh(np.asarray(devs), (axis,))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod ("data", "model"); multi-pod prepends a
    2-way "pod" axis (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_tiny_mesh(*, multi_pod: bool = False):
    """Reduced mesh for CI-scale dry-run validation (8 host devices)."""
    shape = (2, 2, 2) if multi_pod else (2, 4)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)
