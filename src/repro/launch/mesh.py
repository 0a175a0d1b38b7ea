"""Production meshes (v5e): single-pod 16x16 and 2-pod 2x16x16.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state.  Axes are ``Auto`` so ``with_sharding_constraint``
and jit shardings place arrays (``jax.make_mesh`` defaults to ``Explicit``).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / CPU examples)."""
    return _auto_mesh((data, model), ("data", "model"))
