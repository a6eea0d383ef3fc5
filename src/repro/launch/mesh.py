"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax init; everything else
sees the single real CPU device).  Every axis is ``Auto``: the partitioner
propagates the shardings that ``parallel/sharding.py`` pins (JAX's default
``Explicit`` axes would demand an explicit out-sharding on every gather).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 v5e pod (data, model) or 2 pods (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 2):
    """Small mesh: the 2x2 of one four-chip host, or CPU fake devices."""
    return _mesh((n_data, n_model), ("data", "model"))


def make_single_mesh():
    return _mesh((1, 1), ("data", "model"))
