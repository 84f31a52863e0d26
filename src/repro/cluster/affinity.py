"""Affinity backends: phase 1 of the pipeline as pluggable strategies.

Every backend has the signature

    backend(est, x, sigma, mesh) -> NormalizedOperator

where ``est`` is the :class:`~repro.cluster.SpectralClustering` estimator
(carrying k, sparsify_t, dtype, ...), ``x`` is (n, d) points — or, for
``precomputed``, the (n, n) similarity matrix itself, and for ``graph`` a
:class:`~repro.data.graph_file.SparseAdjacency` — and ``sigma`` the RBF
bandwidth (ignored by both).

Backends:
  dense       full row-block similarity (beyond-paper "full" mode): every
              device computes its whole row block; 2x pair-FLOPs, zero
              mirror communication.
  triangular  the paper's balanced upper-triangle block schedule (Alg. 4.2),
              wide row-block storage.
  compact     same schedule, compact per-device tile stacks (perf S1).
  precomputed caller supplies a dense S directly.
  graph       a graph's adjacency as its nonzeros, row by row
              (``graph_file.SparseAdjacency``, paper §5 topology graphs):
              degrees and passes over the nonzeros, never an (n, n)
              matrix.
  knn-topt    dense similarity then top-t row sparsification lifted into the
              distributed path (paper step 1 "and then sparse it"), keeping
              the graph symmetric via max(S, S^T).
  ooc-topt    the same top-t graph built out-of-core by the repro.engine
              map/shuffle/reduce pipeline: chunked Pallas tiles -> spillable
              CSR shards -> shard-streaming matmat (each shard loaded once
              per block); n is bounded by disk, not device memory.
  fused-rbf   matrix-free: a flash-style Pallas kernel recomputes RBF tiles
              in-register on every matmat and applies the D^{-1/2}
              normalization in place, so the similarity matrix NEVER
              exists — affinity memory is O(n*d), and a mixed-precision
              knob (est.compute_dtype) runs the tile products in bf16
              with f32 accumulation.

Every backend returns a NormalizedOperator with a NATIVE matmat — one
pass over its similarity storage per (n_pad, b) block, of any width b
(see operator.py).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.cluster.operator import NormalizedOperator
from repro.cluster.registry import Registry
from repro.core import lanczos as lz, laplacian as lp, similarity as sim
from repro.distrib import mesh_utils
from repro.precision import matmul

AFFINITIES = Registry("affinity")


def _row_constraint(A: jax.Array, mesh) -> jax.Array:
    axes = mesh_utils.flat_axes(mesh)
    return jax.lax.with_sharding_constraint(
        A, NamedSharding(mesh, P(axes, *([None] * (A.ndim - 1)))))


def operator_from_dense(S: jax.Array, n: int, mesh) -> NormalizedOperator:
    """Shared tail for every dense-S backend: pad, row-shard, build the
    shifted operator via :func:`laplacian.make_dense_operator` — a native
    matmat (S stays row-sharded, the (n_pad, b) block replicated, so one
    GSPMD pass of S serves the whole block)."""
    m = mesh_utils.mesh_size(mesh)
    n_pad = mesh_utils.pad_to_multiple(n, m)
    if n_pad != int(S.shape[0]):
        S = jnp.zeros((n_pad, n_pad), S.dtype).at[:n, :n].set(S[:n, :n])
    S = _row_constraint(S, mesh)
    valid = (jnp.arange(n_pad) < n).astype(S.dtype)
    matmat, inv_sqrt = lp.make_dense_operator(S, valid)
    # inv_sqrt threaded through so materializing for eigh doesn't pay a
    # second degree pass over S
    return NormalizedOperator(
        matmat=matmat, valid=valid, inv_sqrt=inv_sqrt, n=n, n_pad=n_pad,
        mesh=mesh, schedule=None,
        dense=lambda: lp.dense_shifted_matrix(S, valid, inv_sqrt))


@AFFINITIES.register("dense")
def dense_affinity(est, x, sigma, mesh) -> NormalizedOperator:
    """Full row-block RBF similarity (the old ``mode="full"`` path)."""
    S = sim.distributed_similarity_full(x, sigma, mesh)  # already padded
    return operator_from_dense(S, int(x.shape[0]), mesh)


@AFFINITIES.register("triangular")
def triangular_affinity(est, x, sigma, mesh) -> NormalizedOperator:
    """Paper-faithful balanced triangular schedule, wide storage."""
    upper = sim.similarity_upper_blocks(x, sigma, mesh)
    deg = lp.degrees(upper)
    matmat = lp.make_shifted_matmat(upper, deg)
    inv_sqrt = lp.masked_inv_sqrt(deg)
    return NormalizedOperator(
        matmat=matmat, valid=upper.diag, inv_sqrt=inv_sqrt,
        n=upper.schedule.n, n_pad=upper.schedule.n_pad, mesh=mesh,
        schedule=upper.schedule,
        dense=lambda: lp.dense_shifted_matrix(sim.materialize(upper),
                                              upper.diag, inv_sqrt))


@AFFINITIES.register("compact")
def compact_affinity(est, x, sigma, mesh) -> NormalizedOperator:
    """Triangular schedule with compact per-device tile stacks."""
    upper = sim.similarity_upper_blocks_compact(x, sigma, mesh)
    deg = sim.sym_matvec_compact(upper, upper.diag)
    inv_sqrt = lp.masked_inv_sqrt(deg)
    valid = upper.diag

    def matmat(V: jax.Array) -> jax.Array:
        SV = sim.sym_matmat_compact(upper, inv_sqrt[:, None] * V)
        return valid[:, None] * V + inv_sqrt[:, None] * SV

    return NormalizedOperator(
        matmat=matmat, valid=valid, inv_sqrt=inv_sqrt,
        n=upper.schedule.n, n_pad=upper.schedule.n_pad, mesh=mesh,
        schedule=upper.schedule,
        dense=lambda: lp.dense_shifted_matrix(sim.materialize_compact(upper),
                                              valid, inv_sqrt))


@AFFINITIES.register("precomputed")
def precomputed_affinity(est, S, sigma, mesh) -> NormalizedOperator:
    """Caller-supplied symmetric non-negative similarity/adjacency matrix."""
    S = jnp.asarray(S, est.dtype)
    if S.ndim != 2 or S.shape[0] != S.shape[1]:
        raise ValueError(
            f"precomputed affinity expects a square (n, n) similarity "
            f"matrix, got shape {tuple(S.shape)}")
    return operator_from_dense(S, int(S.shape[0]), mesh)


@AFFINITIES.register("graph")
def graph_affinity(est, adj, sigma, mesh) -> NormalizedOperator:
    """A graph's adjacency as its nonzeros, kept sparse.

    ``adj`` is a :class:`~repro.data.graph_file.SparseAdjacency`, on the
    host or already on the device.  The degrees are its rows' sums, and
    every pass reads the nonzeros alone
    (:func:`~repro.core.laplacian.make_sparse_operator`); the (n, n)
    matrix exists only if ``eigh`` asks for it (``dense``).  On a mesh of
    several devices the nonzeros are replicated.  Each fit counts once
    in ``affinity.graph_fits``."""
    from repro import obs

    n = adj.n
    n_pad = mesh_utils.pad_to_multiple(n, mesh_utils.mesh_size(mesh))
    cols = jnp.asarray(adj.cols)
    w = jnp.asarray(adj.weights, est.dtype)
    if n_pad != n:          # rows of padding: no nonzeros
        cols = jnp.pad(cols, ((0, n_pad - n), (0, 0)))
        w = jnp.pad(w, ((0, n_pad - n), (0, 0)))
    if mesh_utils.mesh_size(mesh) > 1:
        cols, w = jax.device_put((cols, w), mesh_utils.replicated(mesh))
    valid = (jnp.arange(n_pad) < n).astype(est.dtype)
    matmat, inv_sqrt = lp.make_sparse_operator(cols, w, valid)
    obs.counter("affinity.graph_fits").inc()
    return NormalizedOperator(
        matmat=matmat, valid=valid, inv_sqrt=inv_sqrt, n=n, n_pad=n_pad,
        mesh=mesh, schedule=None,
        dense=lambda: lp.dense_shifted_matrix(lp.sparse_to_dense(cols, w),
                                              valid, inv_sqrt))


@AFFINITIES.register("knn-topt")
def knn_topt_affinity(est, x, sigma, mesh) -> NormalizedOperator:
    """Top-t sparsified RBF graph in the distributed path.

    Rows are sharded, so the per-row top-t threshold is a purely local
    sort; the max(S, S^T) symmetrization is the one transpose (GSPMD
    all-to-all — the Hadoop shuffle analogue).  On a single-device mesh the
    pair computation reuses the Pallas ``rbf_similarity`` kernel.
    """
    n = int(x.shape[0])
    t = est.sparsify_t or max(est.k + 2, 10)
    if mesh_utils.mesh_size(mesh) == 1:
        from repro.kernels import ops as kops
        S = kops.rbf_similarity(x, x, sigma,
                                schedule=getattr(est, "schedule", None))
        S = jnp.asarray(S, est.dtype)
    else:
        S = sim.distributed_similarity_full(x, sigma, mesh)
    # per-row threshold is local to a device (rows are sharded); the
    # max(S, S^T) symmetrization inside sparsify_topt is the one transpose
    St = sim.sparsify_topt(S, int(min(t, n)))
    return operator_from_dense(St, n, mesh)


def _fused_tile(n: int, d: int = 0, itemsize: int = 4) -> int:
    from repro.kernels.fused_rbf_matmat import default_tile
    return default_tile(n, d, itemsize)


class _PassCount:
    """One fused operator's pass and traffic counters, mirrored into
    ``fused.passes{width=<b>}``: fed on the host by the degree pass, by
    each eager ``matmat`` and by the eigensolvers for the passes their
    compiled loops made (``NormalizedOperator.record_passes``)."""

    def __init__(self, pass_bytes):
        self.pass_bytes = pass_bytes
        self.counters = {"matrix_passes": 0, "bytes_streamed": 0}

    def __call__(self, passes: int, width: int) -> None:
        from repro import obs
        self.counters["matrix_passes"] += passes
        self.counters["bytes_streamed"] += passes * self.pass_bytes(width)
        obs.counter("fused.passes", width=str(width)).inc(passes)


@dataclasses.dataclass(frozen=True)
class _FusedPass:
    """The static half of the fused operator: tiles, dtypes and mesh.

    It is the function of the operator's ``matmat``, a
    :class:`jax.tree_util.Partial` over the points, sigma and the scales.
    Being hashable and compared by value (its pass counter left out), it
    lets the Lanczos recurrence take the operator as an argument
    (``core.lanczos.block_run``): every fit of the same shapes and
    schedule reuses one compiled loop, and the persistent compile cache
    can serve it, as it holds no host callback.  A pass traced into a
    compiled program cannot count itself; the program's caller reports
    it (``NormalizedOperator.record_passes``)."""
    bm: int
    bn: int
    bd: int
    compute_dtype: Any
    acc: str
    interpret: bool
    mesh: Any
    count: _PassCount = dataclasses.field(compare=False, repr=False)

    def product(self, xp, sigma, V, row_scale, col_scale):
        """``diag(row_scale) S diag(col_scale) V`` over the padded points,
        row-sharded over the mesh."""
        V = V.astype(jnp.float32)
        if mesh_utils.mesh_size(self.mesh) == 1:   # the kernel IS the pass
            return self.kernel(xp, xp, V, sigma, row_scale, col_scale)
        return _sharded_pass(self, int(V.shape[1]))(
            xp, row_scale[:, None].astype(jnp.float32), V,
            col_scale[:, None].astype(jnp.float32), sigma)

    def kernel(self, x, y, V, sigma, row_scale, col_scale):
        from repro.kernels import fused_rbf_matmat as frm
        return frm.fused_rbf_matmat(
            x, y, V, sigma, row_scale, col_scale, bm=self.bm, bn=self.bn,
            bd=self.bd, compute_dtype=self.compute_dtype, acc=self.acc,
            interpret=self.interpret)

    def __call__(self, xp, sigma, inv_sqrt, valid, V):
        SV = self.product(xp, sigma, V, inv_sqrt, inv_sqrt)
        if not isinstance(V, jax.core.Tracer):      # one eager pass
            self.count(1, int(V.shape[1]))
        return valid[:, None] * V + SV.astype(V.dtype)


@functools.lru_cache(maxsize=None)
def _sharded_pass(fp: _FusedPass, width: int):
    """Row-sharded fused pass for one block width: each device computes
    its (local, b) output stripe from its point rows vs the all-gathered
    columns, then one psum assembles the replicated (n_pad, b) block.
    One jitted pass per (schedule, width), so the shard_map (and the
    interpret-mode kernel on CPU) traces once, not per call."""
    axes = mesh_utils.flat_axes(fp.mesh)
    axis = axes[0] if len(axes) == 1 else axes

    def body(x_local, rs_local, V_full, cs_full, sigma):
        rows_local = x_local.shape[0]
        x_full = lax.all_gather(x_local, axis, tiled=True)
        O_local = fp.kernel(x_local, x_full, V_full, sigma, rs_local[:, 0],
                            cs_full[:, 0])
        out = jnp.zeros((x_full.shape[0], width), jnp.float32)
        out = lax.dynamic_update_slice(
            out, O_local, (lax.axis_index(axis) * rows_local, 0))
        return lax.psum(out, axis)

    # check_vma off: the Pallas kernel's outputs carry no varying-axes
    # annotation for the checker to verify
    return jax.jit(jax.shard_map(
        body, mesh=fp.mesh,
        in_specs=(P(axes, None), P(axes, None), P(), P(), P()),
        out_specs=P(), check_vma=False))


@functools.partial(jax.jit, static_argnums=2)
def _fold_columns(key, valid, b: int):
    """``[1 | R]``: the degree pass's ones column beside (n_pad, b)
    Gaussian columns, zero on padding rows."""
    R = jax.random.normal(key, (valid.shape[0], b), jnp.float32)
    return jnp.concatenate([jnp.ones_like(R[:, :1]), valid[:, None] * R],
                           axis=1)


@jax.jit
def _fold_qr(P, V):
    """From ``P = S_m [1 | R]``: D^{-1/2}, and the start block with its
    triangle, ``D^{1/2} R = Q0 C`` (``lanczos.qr_pos``)."""
    deg = P[:, 0]
    inv_sqrt = lp.masked_inv_sqrt(deg)
    # D^{1/2} = D D^{-1/2}: 0 where the degree is (padding)
    Q0, C = lz.qr_pos((deg * inv_sqrt)[:, None] * V[:, 1:])
    return inv_sqrt, Q0, C


@jax.jit
def _fold_product(P, Q0, inv_sqrt, valid, C_inv):
    """``A Q0 = valid Q0 + D^{-1/2} S_m D^{-1/2} Q0``, where
    ``D^{-1/2} Q0 = R C^{-1}``: the pass's ``S_m R`` times C^{-1}."""
    return valid[:, None] * Q0 + inv_sqrt[:, None] * matmul(P[:, 1:], C_inv)


def _inverse_triangle(C) -> jax.Array:
    """C^{-1} in float64 on the host, the columns of C's dropped
    directions (``qr_pos`` zeroed their rows) zero."""
    C = np.asarray(C, np.float64)
    keep = np.diagonal(C) > 0
    C_inv = np.linalg.inv(C + np.diag(~keep)) * keep[None, :]
    return jnp.asarray(C_inv, jnp.float32)


def _degree_pass(fp: _FusedPass, xp, sigma, valid, start):
    """The degrees' pass, ``S_m 1`` with ``S_m = diag(valid) S
    diag(valid)``: returns D^{-1/2} and, when ``start = (key, b)`` asks
    for it, the start pair ``(Q0, A Q0)`` of a width-b block computed in
    the same pass, over ``[1 | R]``, from Gaussian columns R drawn from
    ``key``; else None.  Q0 is ``D^{1/2} R`` made orthonormal."""
    if start is None:
        P = fp.product(xp, sigma, jnp.ones((xp.shape[0], 1), jnp.float32),
                       valid, valid)
        return lp.masked_inv_sqrt(P[:, 0]), None
    key, b = start
    V = _fold_columns(key, valid, b)
    P = fp.product(xp, sigma, V, valid, valid)
    inv_sqrt, Q0, C = _fold_qr(P, V)
    W0 = _fold_product(P, Q0, inv_sqrt, valid, _inverse_triangle(C))
    return inv_sqrt, (Q0, W0)


def build_fused_rbf_operator(x, sigma, mesh, *, compute_dtype=None,
                             dtype=jnp.float32, schedule=None,
                             start=None) -> NormalizedOperator:
    """Matrix-free shifted normalized operator over raw points.

    Two fused passes, both row-sharded over the mesh with ONE psum each:
    the degree pass (the fused kernel against a ones column, masked to
    valid rows) and then, per matmat call, the normalized product
    ``D^{-1/2} S D^{-1/2} V`` with both scales applied inside the kernel.
    ``start = (key, b)`` widens the degree pass to b + 1 columns: it
    then also returns the first block-Lanczos step's product, on the
    operator's ``start_block`` (:func:`_degree_pass`).
    The (n, n) similarity never exists anywhere — points, scales and the
    (n_pad, b) block are the whole working set.  The points stay on the
    device in their own dtype: bfloat16 rows are not widened (the kernel
    multiplies them exactly, see ``kernels.fused_rbf_matmat``), anything
    else is float32.

    Observability: the degree pass runs under the span
    ``fit.affinity.degree`` (ending when its device work has), every
    executed pass counts once in ``fused.passes{width=<b>}`` (on the
    host: see :class:`_FusedPass`), and the gauge ``fused.d_tiles``
    holds the feature tiles per grid cell.

    Exposed directly (besides ``affinity="fused-rbf"``) so the engine's
    planner can route beyond-dense-memory jobs here without an estimator.

    ``schedule`` takes the estimator-facing domain (None / "default" /
    "auto" / Schedule / dict): tiles, accumulator placement and compute
    dtype of the fused kernel become one searchable value; "auto" consults
    the persistent schedule cache (:mod:`repro.tune.cache`) for this
    (shape bucket, device) and the chosen schedule + source land in the
    operator's ``stats()`` -> estimator ``info_["engine"]``.
    """
    from repro import obs
    from repro.kernels import fused_rbf_matmat as frm
    from repro.tune.schedule import resolve, spec

    n, d = int(x.shape[0]), int(x.shape[1])
    rows = frm.row_dtype(x)
    m = mesh_utils.mesh_size(mesh)
    axes = mesh_utils.flat_axes(mesh)
    tile = _fused_tile(n, d, rows.itemsize)
    sched, sched_src = resolve("fused_rbf_matmat", schedule, bm=tile,
                               bn=tile, compute_dtype=compute_dtype,
                               n=n, m=n, d=d, b=8, itemsize=rows.itemsize)
    bm, bn, bd = sched.bm, sched.bn, sched.bd
    # local row count must divide the row-tile side AND the mesh; padding
    # also covers the column tile (x serves as both sides of the kernel)
    lcm = bm * bn // math.gcd(bm, bn)
    n_pad = mesh_utils.pad_to_multiple(n, m * lcm)
    d_pad = frm.padded_width(d, bd)
    xp = jnp.asarray(x, rows)
    if (n_pad, d_pad) != (n, d):    # zero rows and feature columns
        xp = jnp.zeros((n_pad, d_pad), rows).at[:n, :d].set(xp)
    if m > 1:   # place each device's point rows once, not on every pass
        xp = jax.device_put(xp, NamedSharding(mesh, P(axes, None)))
    valid = (jnp.arange(n_pad) < n).astype(dtype)
    sigma32 = jnp.asarray(sigma, jnp.float32)
    # live HBM-traffic accounting (the dense paths stream n_pad^2 floats
    # per pass; the fused path streams point tiles instead)
    count = _PassCount(lambda width: frm.pass_bytes(
        n_pad, n_pad, d_pad, width, bm=bm, bn=bn, bd=bd,
        itemsize=rows.itemsize))
    fp = _FusedPass(bm=bm, bn=bn, bd=bd, acc=sched.acc,
                    compute_dtype=jnp.dtype(frm.resolve_compute_dtype(
                        sched.compute_dtype or compute_dtype)),
                    interpret=bool(sched.interpret), mesh=mesh, count=count)

    obs.gauge("fused.d_tiles").set(d_pad // bd)
    # pass 1: degrees = S @ 1 with padding masked on both sides
    with obs.span("fit.affinity.degree"):
        inv_sqrt, start_block = jax.block_until_ready(
            _degree_pass(fp, xp, sigma32, valid, start))
        inv_sqrt = inv_sqrt.astype(dtype)
    count(1, 1 if start is None else start[1] + 1)

    matmat = jax.tree_util.Partial(fp, xp, sigma32, inv_sqrt, valid)

    def dense() -> jax.Array:
        # oracle/eigh-only escape hatch: the one place the matrix exists
        from repro.core import similarity as sim_mod
        xf = xp.astype(jnp.float32)
        S = sim_mod.rbf_kernel(xf, xf, sigma32) \
            * valid[:, None] * valid[None, :]
        return lp.dense_shifted_matrix(jnp.asarray(S, dtype), valid,
                                       inv_sqrt)

    # O(n*d) affinity working set vs the dense paths' O(n^2) matrix
    peak = n_pad * d_pad * rows.itemsize + 3 * n_pad * 4 \
        + spec("fused_rbf_matmat").vmem_model(          # + VMEM tiles
            sched, n=n_pad, m=n_pad, d=d_pad, b=1, itemsize=rows.itemsize)

    def stats():
        return dict(count.counters, affinity_peak_bytes=peak,
                    dense_equiv_bytes=n_pad * n_pad * 4,
                    compute_dtype=fp.compute_dtype.name,
                    row_dtype=rows.name, tile=bm,
                    schedule=sched.to_dict(), schedule_source=sched_src)

    baseline = dict(count.counters)  # post-build state: the degree pass

    def reset():
        # restore the post-build baseline so a reused operator reports
        # per-fit passes instead of accumulating across eigensolves
        count.counters.update(baseline)

    return NormalizedOperator(
        matmat=matmat, valid=valid, inv_sqrt=inv_sqrt, n=n, n_pad=n_pad,
        mesh=mesh, schedule=None, dense=dense, stats=stats, reset=reset,
        count_passes=count, start_block=start_block)


def point_dtype(backend, x_dtype, dtype) -> jnp.dtype:
    """The dtype an affinity ``backend`` takes points in: ``dtype``,
    unless the backend carries its own rule as a ``point_dtype(x_dtype,
    dtype)`` attribute."""
    rule = getattr(backend, "point_dtype", None)
    return jnp.dtype(dtype) if rule is None else rule(x_dtype, dtype)


def _fused_point_dtype(x_dtype, dtype) -> jnp.dtype:
    """bfloat16 points stay bfloat16 (the kernel multiplies them
    exactly, ``kernels.fused_rbf_matmat``); others take ``dtype``."""
    return jnp.dtype(jnp.bfloat16) if jnp.dtype(x_dtype) == jnp.bfloat16 \
        else jnp.dtype(dtype)


@AFFINITIES.register("fused-rbf")
def fused_rbf_affinity(est, x, sigma, mesh,
                       start=None) -> NormalizedOperator:
    """Flash-style matrix-free RBF affinity (O(n*d) memory).

    The similarity matrix is recomputed tile-by-tile inside a Pallas
    kernel on every pass and normalized in-register; ``est.compute_dtype``
    ('float32' | 'bfloat16') selects the MXU product precision (f32
    accumulation always).  Runs problem sizes whose dense similarity
    would not fit in memory at in-memory speed — the in-RAM complement
    of ``ooc-topt``.

    Its degrees cost a full pass, so it takes ``start = (key, b)``
    (``folds_start``): that pass then also yields the eigensolver's
    first block product (``build_fused_rbf_operator``).
    """
    return build_fused_rbf_operator(
        x, sigma, mesh, compute_dtype=getattr(est, "compute_dtype", None),
        dtype=est.dtype, schedule=getattr(est, "schedule", None),
        start=start)


fused_rbf_affinity.point_dtype = _fused_point_dtype
fused_rbf_affinity.folds_start = True


@AFFINITIES.register("ooc-topt")
def ooc_topt_affinity(est, x, sigma, mesh) -> NormalizedOperator:
    """Out-of-core top-t graph via the repro.engine MapReduce pipeline.

    The similarity matrix never exists densely: map tasks turn Pallas RBF
    tiles into per-row top-t candidates, the shuffle/reduce stages merge
    them into symmetrized CSR shards spilled to disk under
    ``est.memory_budget``, and the returned operator's matmat streams the
    shards through a host callback (one shard load per block).  Drop-in
    for any eigensolver/assigner.

    Resilience: the build inherits the estimator's retry/speculation
    knobs, and when ``est.stage_timeout_s`` trips (a stage deadline
    expired: queued tasks cancelled, hung attempts abandoned on daemon
    workers, so the deadline bounds this call's wall time) the fit
    degrades gracefully to the in-memory "knn-topt" affinity — the same
    top-t graph built without the engine — instead of failing the job.
    """
    from repro import engine, obs
    from repro.data.chunked import ArrayChunks

    n = int(x.shape[0])
    t = est.sparsify_t or max(est.k + 2, 10)
    plan = engine.JobPlan(
        n=n, chunk_size=est.chunk_size or 1024, t=int(min(t, n)), k=est.k,
        sigma=float(sigma), memory_budget=est.memory_budget,
        spill_dir=est.spill_dir, seed=est.seed,
        workers=getattr(est, "workers", 1),
        prefetch_depth=getattr(est, "prefetch_depth", 2),
        max_retries=getattr(est, "max_retries", 2),
        speculation_factor=getattr(est, "speculation_factor", 0.0),
        stage_timeout_s=getattr(est, "stage_timeout_s", None),
        faults=getattr(est, "faults", None))
    reader = ArrayChunks(np.asarray(x), plan.chunk_size)
    try:
        graph, _sigma = engine.build_graph(reader, plan)
    except engine.EngineTimeoutError as e:
        obs.counter("engine.path_fallbacks").inc()
        est._affinity_fallback = f"ooc-topt->knn-topt ({e})"
        return AFFINITIES.get("knn-topt")(est, x, sigma, mesh)
    # same padding invariant as the dense backends: downstream shard_map
    # stages need row counts divisible by the mesh
    n_pad = mesh_utils.pad_to_multiple(n, mesh_utils.mesh_size(mesh))
    return engine.make_normalized_operator(graph, dtype=est.dtype, mesh=mesh,
                                           pad_to=n_pad)
