"""Mesh / sharding helpers shared by the spectral-clustering core and the LM stack.

The paper row-shards its matrices over HBase region servers; here the analogue
is a NamedSharding over one or more mesh axes.  All helpers are functions (no
module-level jax device access) so importing never touches device state.
"""
from __future__ import annotations

import math
from typing import Sequence

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P


def make_mesh(shape: Sequence[int], axis_names: Sequence[str], devices=None) -> Mesh:
    """jax.make_mesh pinned to Auto axis types."""
    return jax.make_mesh(tuple(shape), tuple(axis_names), devices=devices,
                         axis_types=(AxisType.Auto,) * len(axis_names))


def local_mesh(axis_name: str = "rows", n_devices: int | None = None) -> Mesh:
    """A 1-D mesh over all (or the first ``n_devices``) local devices."""
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return make_mesh((len(devs),), (axis_name,), devices=devs)


def mesh_size(mesh: Mesh, axes: Sequence[str] | None = None) -> int:
    if axes is None:
        return math.prod(mesh.shape.values())
    return math.prod(mesh.shape[a] for a in axes)


def flat_axes(mesh: Mesh) -> tuple[str, ...]:
    """All axis names of a mesh, for sharding over the flattened device set."""
    return tuple(mesh.axis_names)


def row_sharding(mesh: Mesh, ndim: int = 2, axes: Sequence[str] | None = None) -> NamedSharding:
    """Shard dim 0 over ``axes`` (default: every mesh axis), replicate the rest."""
    axes = tuple(axes) if axes is not None else flat_axes(mesh)
    spec = P(axes, *([None] * (ndim - 1)))
    return NamedSharding(mesh, spec)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def pad_to_multiple(n: int, m: int) -> int:
    """Smallest multiple of ``m`` that is >= ``n``."""
    return ((n + m - 1) // m) * m
