"""The jax sharding API surface, in one place.

Written against the installed jax (0.9): ``jax.shard_map``,
``jax.make_mesh(..., axis_types=...)``, ``jax.sharding.set_mesh`` and
``AbstractMesh(axis_sizes, axis_names)``.  Everything sharding-adjacent
goes through this module so a future API move is a change in one file:

  shard_map(f, mesh=..., in_specs=..., out_specs=..., check_vma=False)
  make_mesh(shape, axes, axis_types=None, devices=None)
  abstract_mesh(shape, axes)  -- device-free mesh for tracing/auditing
  set_mesh(mesh)          -- context manager
  ambient_mesh()          -- abstract mesh if set, else None
  mesh_is_auto(mesh)      -- True iff every axis is Auto
  auto_axes(n)            -- n Auto axis types
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AbstractMesh, AxisType, Mesh

__all__ = ["shard_map", "make_mesh", "abstract_mesh", "set_mesh",
           "ambient_mesh", "mesh_is_auto", "auto_axes", "AxisType"]


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = False):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str],
              axis_types=None, devices=None) -> Mesh:
    """``jax.make_mesh``; ``axis_types`` must name one type per axis."""
    if axis_types is not None and len(axis_types) != len(axis_names):
        raise ValueError(
            f"{len(axis_types)} axis_types for {len(axis_names)} mesh "
            f"axes {tuple(axis_names)}")
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=None if axis_types is None
                         else tuple(axis_types), devices=devices)


def abstract_mesh(axis_shapes: Sequence[int],
                  axis_names: Sequence[str]) -> AbstractMesh:
    """A device-free mesh: collectives trace over it without devices."""
    return AbstractMesh(tuple(axis_shapes), tuple(axis_names))


def auto_axes(n: int):
    """n Auto axis types (for forwarding into make_mesh)."""
    return (AxisType.Auto,) * n


def set_mesh(mesh: Mesh):
    """Ambient-mesh scope (``jax.sharding.set_mesh``)."""
    return jax.sharding.set_mesh(mesh)


def ambient_mesh():
    """The mesh of the enclosing set_mesh scope, or None."""
    m = jax.sharding.get_abstract_mesh()
    if m is None or not m.axis_names:
        return None
    return m


def mesh_is_auto(mesh) -> bool:
    """True iff no axis of ``mesh`` is Manual/Explicit."""
    return all(t == AxisType.Auto for t in mesh.axis_types)
