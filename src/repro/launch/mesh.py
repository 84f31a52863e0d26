"""Production mesh builders (functions, never module-level constants, so
importing this module never touches jax device state).

Mesh construction goes through :func:`repro.distrib.mesh_utils.make_mesh`,
which pins every axis to ``AxisType.Auto``.
"""
from __future__ import annotations

from jax.sharding import Mesh

from repro.distrib import mesh_utils


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single pod: (16, 16) = 256 chips, axes (data, model).
    Multi-pod: (2, 16, 16) = 512 chips, axes (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return mesh_utils.make_mesh(shape, axes)


def make_spectral_mesh(*, multi_pod: bool = False) -> Mesh:
    """The spectral pipeline row-shards its matrices over every chip: a
    flat 1-D mesh (the Hadoop "all workers" pool)."""
    n = 512 if multi_pod else 256
    return mesh_utils.make_mesh((n,), ("rows",))
