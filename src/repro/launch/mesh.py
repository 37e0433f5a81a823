"""Production mesh construction.

FUNCTIONS (not module-level constants) so importing never touches jax
device state. Pod = AI-DC: the "pod" axis is the long-haul OTN boundary that
MatchRDMA manages; "data" x "model" is the intra-DC 2D layout. Every axis is
``AxisType.Auto``: GSPMD partitions what ``shard_map`` leaves automatic.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_mesh(shape, axes, devices=None) -> Mesh:
    """An all-Auto mesh of ``shape`` over ``devices`` (default: the
    devices ``jax.make_mesh`` picks from ``jax.devices()``)."""
    shape, axes = tuple(shape), tuple(axes)
    types = (AxisType.Auto,) * len(axes)
    if devices is not None:
        return Mesh(np.asarray(devices).reshape(shape), axes,
                    axis_types=types)
    return jax.make_mesh(shape, axes, axis_types=types)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh_for(par, devices=None):
    """Mesh from a ParallelConfig (tests / small runs pass explicit devices)."""
    return make_mesh(par.mesh_shape(), par.axis_names(), devices)
