"""Mesh construction: every mesh in the repo is built here.

``jax.make_mesh`` defaults to ``Explicit`` axes, under which a gather on a
sharded operand must name its output sharding.  The engines rely on the
compiler to propagate shardings (shard_map bodies, replicated gathers), so
meshes are built with ``Auto`` axes.

FUNCTIONS, not module-level constants: importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before the first jax call).
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], devices=None):
    """``jax.make_mesh`` with ``Auto`` axis types (compiler-propagated
    shardings); ``devices`` defaults to ``jax.devices()``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(data: int = 2, model: int = 2, pod: int = 0):
    """Small mesh for CPU tests (host-device-count permitting)."""
    if pod:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))
