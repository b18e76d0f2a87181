"""Mesh and shard_map helpers for the manual-collectives layer.

Everything that builds meshes or shard_maps goes through these two
helpers, so replication checking and axis types are set in one place.
"""
from __future__ import annotations

from typing import Sequence

import jax


def shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str]):
    """``jax.make_mesh`` with Auto axis types."""
    names = tuple(axis_names)
    return jax.make_mesh(tuple(axis_shapes), names,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(names))
