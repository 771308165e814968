"""The jax sharding API as the solvers call it (jax 0.9).

One home for the three spellings the distributed engine, the mesh helpers
and the legacy-solver oracle share, so a jax API change lands here once.
"""
from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = False):
    """``jax.shard_map`` with the VMA check off by default: the solvers
    return post-all-gather replicas whose bitwise equality across workers
    the type system cannot prove."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def pvary(x, axis_names):
    """Mark ``x`` device-varying along ``axis_names``."""
    return jax.lax.pcast(x, axis_names, to="varying")


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(
        axis_shapes, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))
