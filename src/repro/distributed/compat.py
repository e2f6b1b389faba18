"""Mesh-mapping helpers shared by the distributed layer.

Everything that maps over a mesh goes through ``shard_map`` here, so the
replication-check setting lives in one place.
"""
from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def axis_size(axis_name: str) -> int:
    """Static size of a mesh axis from inside shard_map."""
    return jax.lax.axis_size(axis_name)
