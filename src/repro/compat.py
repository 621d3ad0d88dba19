"""Thin wrappers over the jax mesh / shard_map API (jax 0.9).

Every module imports these three helpers from here instead of calling jax
directly, so the replication-check and axis-type choices live in one
place.  The wrappers disable replication/vma checking: our custom_vjp
adjoints intentionally produce replication patterns the checker cannot
infer (see DESIGN.md §2).
"""

from __future__ import annotations

import jax

__all__ = ["shard_map", "axis_size", "make_mesh"]


def shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def axis_size(axis_name) -> int:
    return jax.lax.axis_size(axis_name)


def make_mesh(axis_shapes, axis_names, devices=None):
    """jax.make_mesh with Auto axis types.

    ``devices`` restricts the mesh to an explicit device subset (the
    elastic mesh-shrink path builds degraded meshes over the survivors of
    a simulated device loss); None keeps jax's default device assignment.
    """
    kw = {"devices": devices} if devices is not None else {}
    return jax.make_mesh(
        tuple(axis_shapes), tuple(axis_names),
        axis_types=(jax.sharding.AxisType.Auto,) * len(tuple(axis_names)),
        **kw)
