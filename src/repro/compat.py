"""Mesh construction with the axis types this repo relies on.

Every mesh here uses ``AxisType.Auto`` axes: shardings are propagated by
the compiler and ``shard_map`` bodies take explicit ``in_specs``.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names))
