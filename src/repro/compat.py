"""Thin mesh and collective helpers shared by the executors and launchers.

  ``make_mesh(shape, axes)``   — ``jax.make_mesh`` with every axis typed
                                 ``Auto`` (sharding left to the compiler).
  ``all_to_all(x, axes)``      — tiled ``jax.lax.all_to_all`` over one
                                 shard_map axis or a tuple of axes.

Everything else comes straight from ``jax``: ``jax.set_mesh``,
``jax.shard_map`` and ``jax.lax.pcast``.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["all_to_all", "make_mesh"]


def make_mesh(shape, axes) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def all_to_all(x, axis_name, *, split_axis: int = 0, concat_axis: int = 0):
    """Tiled ``jax.lax.all_to_all`` over one shard_map axis (or a tuple of
    axes, collectived jointly).  ``x`` is the local ``(S, ...)`` lane
    stack: lane ``s`` of the result is what shard ``s`` addressed to this
    shard — the batched per-shard-group exchange the coded executor's
    residual combining runs on."""
    name = axis_name if not (isinstance(axis_name, (tuple, list))
                             and len(axis_name) == 1) else axis_name[0]
    return jax.lax.all_to_all(x, name, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=True)
