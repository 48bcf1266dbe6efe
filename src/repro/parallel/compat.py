"""The mesh APIs the sharding layer uses, under one import path.

Plain re-exports of the jax (0.9) spellings — ``jax.shard_map``,
``jax.sharding.AxisType``, ``jax.make_mesh(..., axis_types=)``,
``jax.set_mesh``, ``jax.lax.axis_size``, ``jax.sharding.get_abstract_mesh``
— plus two small conveniences built on them.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh, get_abstract_mesh

__all__ = ["AxisType", "make_mesh", "mesh_from_devices", "set_mesh",
           "get_abstract_mesh", "shard_map", "shard_map_norep", "axis_size"]

shard_map = jax.shard_map
make_mesh = jax.make_mesh
set_mesh = jax.set_mesh
axis_size = jax.lax.axis_size


def shard_map_norep(f, **kw):
    """``shard_map`` with the replication checker off — required when the
    body contains ops without a replication rule (``pallas_call``, the
    interpret-mode local sorts of ``core/distributed``)."""
    return jax.shard_map(f, check_vma=False, **kw)


def mesh_from_devices(devices, axis_names, axis_types=None):
    """``jax.sharding.Mesh`` from an explicit device array."""
    return Mesh(devices, axis_names, axis_types=axis_types)
