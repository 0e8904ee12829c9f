"""Mesh helpers over jax's sharding surface.

``shard_map`` is ``jax.shard_map``; :func:`make_mesh` and
:func:`abstract_mesh` fix the two conventions the rest of the tree relies
on — every mesh axis Auto-typed, and a small mesh taking the first devices
of a process that has more.
"""
from __future__ import annotations

import math
from typing import Sequence

import jax
from jax.sharding import AbstractMesh, AxisType

__all__ = ["shard_map", "make_mesh", "abstract_mesh"]

shard_map = jax.shard_map


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str],
              *, devices=None) -> "jax.sharding.Mesh":
    """``jax.make_mesh`` with all axes Auto-typed.

    Slices ``jax.devices()`` down to the mesh size when ``devices`` is not
    given — a (1, 1) test mesh must work inside a subprocess that forced
    8 host devices.
    """
    if devices is None:
        devices = jax.devices()[:math.prod(tuple(axis_shapes))]
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         devices=devices,
                         axis_types=(AxisType.Auto,) * len(tuple(axis_names)))


def abstract_mesh(axis_shapes: Sequence[int],
                  axis_names: Sequence[str]) -> AbstractMesh:
    """Device-free mesh (sharding-spec rules only read shape/axis names)."""
    return AbstractMesh(tuple(axis_shapes), tuple(axis_names))
