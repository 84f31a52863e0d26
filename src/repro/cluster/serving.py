"""Serving-side out-of-sample extension: the fused Nystrom transform.

``SpectralClustering.transform`` embeds m new points into the fitted
spectral space via the Nystrom extension

    z(x) = D_new^{-1/2} K(x, X_train) D_train^{-1/2} Z / mu

The straightforward implementation materializes the (m, n) query-vs-train
RBF kernel — O(m*n) memory, which undoes everything the fused-rbf affinity
bought at fit time the moment the model is served against real traffic.
This module provides the matrix-free path: one pass of the dual-output
Pallas kernel (:func:`repro.kernels.ops.fused_nystrom_matmat`) streams
(bm, d) query tiles against (bn, d) training tiles, builds the RBF entries
in-register, and accumulates BOTH ``K @ (D_train^{-1/2} Z)`` and the query
degree column ``K @ 1`` — so transform/predict memory is
O((m + n)·d + n·k) and the kernel matrix never exists.

Routing mirrors :func:`repro.engine.plan.route_path`: the dense path is
kept for small problems (one jnp matmul beats a tiled interpret-mode
kernel there), the fused path takes over once the (m, n) kernel would
outgrow the budget.  On a multi-device mesh the fused pass row-shards the
QUERIES via ``shard_map`` — each device embeds its own query stripe
against the replicated training set, no collective needed.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import kmeans as km, laplacian as lp
from repro.distrib import mesh_utils

TRANSFORM_PATHS = ("auto", "dense", "fused")


class RequestRejected(RuntimeError):
    """Base class for typed serving admission-control rejections (the
    load-shedding contract of ``launch.cluster_serve.ClusterServer``)."""
    status = "rejected"


class QueueFullError(RequestRejected):
    """Admission denied: accepting the request would push the pending-row
    backlog past the server's bounded admission queue."""
    status = "shed"

    def __init__(self, rid: int, rows: int, pending_rows: int,
                 max_pending_rows: int):
        super().__init__(
            f"request {rid} shed: {rows} rows would push the pending "
            f"backlog ({pending_rows} rows) past the admission bound "
            f"({max_pending_rows} rows)")
        self.rid = rid
        self.rows = rows
        self.pending_rows = pending_rows
        self.max_pending_rows = max_pending_rows


class DeadlineExceededError(RequestRejected):
    """An admitted request sat past its deadline before completing; its
    remaining rows are dropped from the batch window."""
    status = "expired"

    def __init__(self, rid: int, deadline_s: float, waited_s: float):
        super().__init__(f"request {rid} expired: waited {waited_s:.3f}s "
                         f"against a {deadline_s:g}s deadline")
        self.rid = rid
        self.deadline_s = deadline_s
        self.waited_s = waited_s

# default ceiling on the materialized (m, n) query-vs-train kernel when the
# estimator carries no memory_budget: 64 MiB ~= the m = n = 4096 f32 kernel
# (same spirit as engine.route_path, which routes on the dense similarity)
DENSE_TRANSFORM_MAX_BYTES = 64 * 1024 * 1024


def check_transform_path(path: str) -> str:
    if path not in TRANSFORM_PATHS:
        raise ValueError(f"transform_path must be one of {TRANSFORM_PATHS}, "
                         f"got {path!r}")
    return path


def route_transform(n: int, m: int, *, path: str = "auto",
                    memory_budget: Optional[int] = None,
                    itemsize: int = 4) -> str:
    """Pick the transform path for m queries against n training points.

    A forced ``path`` ("dense" / "fused") wins.  With ``path="auto"`` the
    materialized (m, n) kernel's bytes decide: under the budget (the
    estimator's ``memory_budget``, else :data:`DENSE_TRANSFORM_MAX_BYTES`)
    the dense path is kept — one jnp matmul, no tiling overhead; over it,
    the fused kernel streams the training tiles instead.  Both paths
    compute the same extension (fused-vs-dense parity is a test contract,
    <= 1e-4 in f32)."""
    check_transform_path(path)
    if path != "auto":
        return path
    budget = memory_budget if memory_budget is not None \
        else DENSE_TRANSFORM_MAX_BYTES
    return "dense" if m * n * itemsize <= budget else "fused"


def transform_tile(n: int) -> int:
    """MXU-aligned tile side for the serving kernel — the one fit-side
    rule, shared so retuning it can never split the two paths."""
    from repro.kernels.fused_rbf_matmat import default_tile
    return default_tile(n)


def transform_peak_bytes(m: int, n: int, d: int, k: int, *,
                         tile: Optional[int] = None, mesh_size: int = 1,
                         itemsize: int = 4) -> int:
    """Working-set model of one fused transform: padded queries + training
    points + the (n, k) eigenvector block + the (m, k+1) outputs + scale
    columns, plus the VMEM tiles — compare against the dense path's
    ``m * n * itemsize`` kernel matrix.  ``mesh_size`` matters: on a mesh
    the queries pad to a multiple of ``mesh_size * tile`` (every device's
    stripe must divide the row tile), exactly like ``fused_transform``."""
    t = tile or transform_tile(max(m, n))
    m_pad = mesh_utils.pad_to_multiple(m, max(1, mesh_size) * t)
    n_pad = mesh_utils.pad_to_multiple(n, t)
    host = (m_pad * d + n_pad * d + n_pad * (k + 2) + m_pad * (k + 1)) \
        * itemsize
    vmem = (2 * t * d + t * t + t * (k + 3)) * itemsize
    return host + vmem


def extension_from_product(O: jax.Array, deg: jax.Array,
                           mu: jax.Array) -> jax.Array:
    """Finish the Nystrom extension from the fused pass outputs: apply the
    query-side D^{-1/2} (zero-degree queries — points far from every
    training point — pin to the all-zero row instead of NaN), divide by
    the operator eigenvalues, unit-normalize rows."""
    inv_new = lp.masked_inv_sqrt(deg)
    emb = (inv_new[:, None] * O) / mu[None, :]
    return km.normalize_rows(emb)


def shifted_mu(eigenvalues: jax.Array) -> jax.Array:
    """Eigenvalues of the normalized similarity N = D^{-1/2} S D^{-1/2}
    from the stored L_sym eigenvalues, clamped away from zero (shared by
    the dense and fused transform paths)."""
    mu = 1.0 - eigenvalues
    return jnp.where(jnp.abs(mu) > 1e-6, mu, 1e-6)


def fused_transform(x: jax.Array, train_x: jax.Array, eigvecs: jax.Array,
                    inv_sqrt: jax.Array, sigma, mu: jax.Array, *,
                    mesh: Any = None, compute_dtype=None,
                    interpret: bool | None = None, schedule=None,
                    _cache: Optional[dict] = None,
                    _info: Optional[dict] = None) -> jax.Array:
    """Matrix-free Nystrom embedding of ``x`` (m, d) -> (m, k).

    Single-device: one padded call of the dual-output kernel.  Multi-
    device: queries are row-sharded over the mesh via ``shard_map`` and
    each device streams the replicated training set against its own query
    stripe — output rows are disjoint, so there is no collective at all
    (the fit-side fused pass needs one psum because there the OPERATOR
    rows are sharded; here the query rows are).

    ``_cache`` (optional dict) memoizes the jitted sharded pass per
    (mesh, shape) key so a serving loop pays one trace, not one per batch.

    ``schedule`` (None / "default" / "auto" / Schedule / dict) selects the
    serving kernel's tiles/dtype/accumulator; "auto" consults the
    persistent schedule cache for this shape bucket and device.
    """
    from repro.kernels import fused_rbf_matmat as frm
    from repro.kernels import ops as kops
    from repro.tune.schedule import resolve

    mesh = mesh or mesh_utils.local_mesh("rows")
    m, d = int(x.shape[0]), int(x.shape[1])
    n, k = int(eigvecs.shape[0]), int(eigvecs.shape[1])
    tile = transform_tile(max(m, n))
    msize = mesh_utils.mesh_size(mesh)
    sigma32 = jnp.asarray(sigma, jnp.float32)
    sched, _src = resolve("fused_nystrom_matmat", schedule, bm=tile,
                          bn=tile, compute_dtype=compute_dtype,
                          interpret=interpret, n=n, m=m, d=d, b=k)
    if _info is not None:   # caller-visible record of what actually ran
        _info["schedule"] = sched.to_dict()
        _info["schedule_source"] = _src

    if msize == 1:
        O, deg = kops.fused_nystrom_matmat(
            x, train_x, eigvecs, sigma32, inv_sqrt, None, schedule=sched)
        return extension_from_product(O, deg, mu)

    axes = mesh_utils.flat_axes(mesh)
    # queries pad to (mesh x row tile) so every device's stripe divides the
    # row tile; training-side padding is column-tile-only (replicated)
    m_pad = mesh_utils.pad_to_multiple(m, msize * sched.bm)
    n_pad = mesh_utils.pad_to_multiple(n, sched.bn)
    cdtype = frm.resolve_compute_dtype(sched.compute_dtype)

    key = ("nystrom", mesh, m_pad, n_pad, d, k, sched.bm, sched.bn,
           jnp.dtype(cdtype).name, sched.acc, sched.interpret)
    fn = _cache.get(key) if _cache is not None else None
    if fn is None:
        def body(xq_local, y_full, Z_full, cs_full, cv_full, sig):
            return frm.fused_nystrom_matmat(
                xq_local, y_full, Z_full, sig, cs_full[:, 0], cv_full[:, 0],
                bm=sched.bm, bn=sched.bn, compute_dtype=cdtype,
                acc=sched.acc, interpret=sched.interpret)

        # check_vma off: the Pallas kernel's outputs carry no
        # varying-axes annotation for the checker to verify
        fn = jax.jit(jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(axes, None), P(), P(), P(), P(), P()),
            out_specs=(P(axes, None), P(axes, None)), check_vma=False))
        if _cache is not None:
            _cache[key] = fn

    xq = jnp.zeros((m_pad, d), jnp.float32).at[:m].set(
        jnp.asarray(x, jnp.float32))
    yp = jnp.zeros((n_pad, d), jnp.float32).at[:n].set(
        jnp.asarray(train_x, jnp.float32))
    Zp = jnp.zeros((n_pad, k), jnp.float32).at[:n].set(
        jnp.asarray(eigvecs, jnp.float32))
    cs = jnp.zeros((n_pad, 1), jnp.float32).at[:n, 0].set(
        jnp.asarray(inv_sqrt, jnp.float32))
    cv = jnp.zeros((n_pad, 1), jnp.float32).at[:n, 0].set(1.0)
    O, deg = fn(xq, yp, Zp, cs, cv, sigma32)
    return extension_from_product(O[:m], deg[:m, 0], mu)
