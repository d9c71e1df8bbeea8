"""Mesh construction with ``Auto`` axis types.

``jax.make_mesh`` defaults to ``Explicit`` axis types, under which
``with_sharding_constraint`` may only name ``Auto`` axes.  This codebase
shards through ``with_sharding_constraint`` and ``shard_map`` with the
mesh's axes left to the compiler, so every mesh is built here, ``Auto``
on every axis unless the caller asks otherwise.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str],
              *, axis_types: Optional[Sequence[AxisType]] = None,
              devices=None):
    """``jax.make_mesh`` with ``AxisType.Auto`` on every axis by default."""
    if axis_types is None:
        axis_types = (AxisType.Auto,) * len(axis_names)
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=tuple(axis_types), devices=devices)
