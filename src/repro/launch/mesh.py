"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (jax locks the device count on first backend
init, and only launch/dryrun.py is allowed to force 512 host devices).

Every axis is Auto: the model code places activations with
``with_sharding_constraint`` through ``AxisRules``, which an Explicit
axis (``jax.make_mesh``'s default) rejects.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple, axes: tuple):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2):
    """Small mesh for CPU tests (requires >= data*model host devices)."""
    return _auto_mesh((data, model), ("data", "model"))


def dp_axes_of(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
