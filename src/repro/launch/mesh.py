"""Mesh construction for the production pod slices and FDN target platforms.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state.  Every axis is
``AxisType.Auto``: the model code shards through GSPMD annotations.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(model_parallel: Optional[int] = None) -> Mesh:
    """Mesh over whatever devices exist (CPU tests: 1 device)."""
    n = jax.device_count()
    mp = model_parallel or 1
    return make_mesh((n // mp, mp), ("data", "model"))
