"""Mesh and shard_map helpers with the repo's fixed choices.

Every mesh in this repo wants plain ``Auto`` axes, and every shard_map takes
its arguments by keyword. Use ``compat.make_mesh(shape, axes)`` /
``compat.shard_map(...)`` instead of repeating those choices at each call
site.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              *, devices=None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto axis types."""
    kwargs = {} if devices is None else {"devices": devices}
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes), **kwargs)


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool | None = None):
    """``jax.shard_map``; ``check_vma=None`` keeps jax's default."""
    kwargs = {} if check_vma is None else {"check_vma": check_vma}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)
