"""SPMD helpers shared by graph placement (`core.graph.Graph.place`,
`per_partition`) and the SPMD test lane (tests/spmd_check.py), so the mesh
and shard_map spelling lives in one place."""
from __future__ import annotations

import jax


def make_mesh(shape, names, devices=None):
    """A mesh with explicit Auto axis types over `devices` (default: all)."""
    kw = {} if devices is None else {"devices": devices}
    return jax.make_mesh(shape, names,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(names),
                         **kw)


def shard_map(fn, mesh, in_specs, out_specs):
    """jax.shard_map without the replication check: engine steps return
    per-partition values the checker cannot prove replicated."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
