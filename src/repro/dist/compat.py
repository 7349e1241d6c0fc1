"""Thin wrappers over jax's distribution APIs.

Everything in repro that builds meshes or calls shard_map goes through this
module, so a change in those APIs (jax 0.9 made ``AbstractMesh`` take axis
sizes and names as two sequences) is absorbed in one place and call sites
stay as they are.
"""
from __future__ import annotations

import contextlib

import jax


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool | None = None):
    """``jax.shard_map``; ``check_vma=None`` keeps jax's default."""
    kwargs = {} if check_vma is None else {"check_vma": check_vma}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)


def axis_types_auto(n: int):
    """``(AxisType.Auto,) * n``."""
    return (jax.sharding.AxisType.Auto,) * n


def make_mesh(axis_shapes, axis_names, *, devices=None, axis_types=None):
    kwargs = {}
    if devices is not None:
        kwargs["devices"] = devices
    if axis_types is not None:
        kwargs["axis_types"] = axis_types
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names), **kwargs)


def abstract_mesh(axis_shapes, axis_names) -> jax.sharding.AbstractMesh:
    """A device-free mesh of the given axis sizes and names, for tracing a
    sharded program at a shard count the host does not have."""
    return jax.sharding.AbstractMesh(tuple(axis_shapes), tuple(axis_names))


@contextlib.contextmanager
def set_mesh(mesh):
    """Activate ``mesh`` for jit tracing under it: ``jax.set_mesh``, plus
    ``repro.dist.sharding``'s ambient stack so ``constrain`` resolves it."""
    from repro.dist import sharding
    with jax.set_mesh(mesh), sharding.use_mesh(mesh):
        yield mesh
