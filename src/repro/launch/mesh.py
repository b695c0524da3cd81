"""Mesh construction. ``make_production_mesh`` is a FUNCTION (never a
module-level constant) so importing this module touches no jax device state.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (e.g. (2, 2) x ('pod', 'data')) over the first
    ``prod(shape)`` devices. A mesh larger than the device count is an
    error naming both numbers."""
    shape, axes = tuple(shape), tuple(axes)
    devices = jax.devices()
    need = math.prod(shape)
    if need > len(devices):
        raise ValueError(f"mesh {'x'.join(map(str, shape))} needs {need} "
                         f"devices, but only {len(devices)} are available")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices[:need])
