"""Mesh and shard_map helpers with the defaults this repo relies on."""
from __future__ import annotations

import jax


def make_mesh(axis_shapes, axis_names, *, explicit: bool = False, **kw):
    """jax.make_mesh with Auto axes unless ``explicit``: jax.make_mesh
    defaults to Explicit axes, under which the gathers and sharding
    constraints of the models and the analytics engine do not trace."""
    types = (jax.sharding.AxisType.Explicit if explicit
             else jax.sharding.AxisType.Auto,) * len(axis_names)
    return jax.make_mesh(axis_shapes, axis_names, axis_types=types, **kw)


def require_auto_axes(mesh, who: str) -> None:
    """Refuse a mesh with Explicit axes (see :func:`make_mesh`)."""
    explicit = [n for n, t in zip(mesh.axis_names, mesh.axis_types)
                if t == jax.sharding.AxisType.Explicit]
    if explicit:
        raise ValueError(
            f"{who} needs a mesh with Auto axes; axes {explicit} are "
            "Explicit. Build it with repro.compat.make_mesh (or "
            "jax.make_mesh(..., axis_types=(AxisType.Auto,) * n)).")


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = False):
    """jax.shard_map with the replication check off by default."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)
