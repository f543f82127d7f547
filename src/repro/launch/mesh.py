"""Production mesh construction.

Target: TPU v5e. Single pod = 16 x 16 = 256 chips, axes ("data", "model");
multi-pod = 2 x 16 x 16 = 512 chips, axes ("pod", "data", "model").
Defined as a function so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax import).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_axes(n: int) -> tuple:
    """Every mesh here has Auto axes: the programs place arrays through
    in/out shardings, sharding constraints and shard_map (GSPMD style).
    ``jax.make_mesh`` defaults to Explicit axes, which carry shardings in
    the array types and reject this code's gathers and scatters."""
    return (AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, _auto_axes(len(axes)))


def make_debug_mesh(data: int = 1, model: int = 1):
    """Small mesh over the first data * model devices (tests, one host)."""
    return jax.make_mesh((data, model), ("data", "model"), _auto_axes(2))


def n_chips(mesh) -> int:
    import numpy as np
    return int(np.prod(list(dict(mesh.shape).values())))
