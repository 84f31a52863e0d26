"""Phase 1 of the paper: parallel similarity-matrix construction.

The paper computes only the upper triangle of the RBF similarity matrix
(S is symmetric) and balances load by assigning row ``i`` and row ``n-i+1``
to the same worker (Alg. 4.2).  On a TPU mesh the same idea becomes a
*block-triangular schedule*: the ``n`` (padded) rows are split into ``2m``
blocks (``m`` = number of devices); device ``d`` owns blocks ``d`` and
``2m-1-d``, so every device computes exactly ``2m+1`` upper-triangle tiles
of size ``b×b`` — perfectly balanced, like the paper's pairing.

Rows are stored *block-permuted* so each device's two blocks are contiguous
(a NamedSharding over dim 0).  Columns stay in the same permuted order, so
the result ``U`` is the masked upper triangle of the (permuted) similarity
matrix: S_perm = U + Uᵀ - diag(U).

Two execution modes:
  * ``triangular`` (paper-faithful): each unordered pair computed once;
    downstream consumers either materialize S (transpose = all-to-all,
    like Hadoop's shuffle) or use :func:`sym_matvec` which never
    materializes the mirror (beyond-paper optimization).
  * ``full`` (beyond-paper trade): every device computes its whole row
    block — 2x the pair-FLOPs, but zero mirror communication and no
    permutation bookkeeping.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distrib import mesh_utils
from repro.precision import matmul


# ---------------------------------------------------------------------------
# Dense / reference pieces (also used inside the sharded kernels)
# ---------------------------------------------------------------------------

def pairwise_sq_dists(x: jax.Array, y: jax.Array) -> jax.Array:
    """||x_i - y_j||^2 via the MXU-friendly decomposition."""
    xx = jnp.sum(x * x, axis=-1)[:, None]
    yy = jnp.sum(y * y, axis=-1)[None, :]
    xy = matmul(x, y.T)
    return jnp.maximum(xx + yy - 2.0 * xy, 0.0)


def rbf_kernel(x: jax.Array, y: jax.Array, sigma: float | jax.Array) -> jax.Array:
    """S_ij = exp(-||x_i-y_j||^2 / (2 sigma^2))  (paper §3.2.3)."""
    return jnp.exp(-pairwise_sq_dists(x, y) / (2.0 * sigma**2))


def dense_similarity(x: jax.Array, sigma: float | jax.Array) -> jax.Array:
    return rbf_kernel(x, x, sigma)


def median_sigma(x: jax.Array, sample: int = 1024) -> jax.Array:
    """Median-distance heuristic for the RBF bandwidth (float32 from
    bfloat16 points)."""
    xs = x[: min(sample, x.shape[0])]
    if xs.dtype == jnp.bfloat16:
        xs = xs.astype(jnp.float32)
    d2 = pairwise_sq_dists(xs, xs)
    n = d2.shape[0]
    off = d2[jnp.triu_indices(n, k=1)]
    return jnp.sqrt(jnp.median(off) + 1e-12)


def sparsify_topt(S: jax.Array, t: int) -> jax.Array:
    """Keep the top-``t`` entries per row (paper step 1 "and then sparse it"),
    then symmetrize with max(S, S^T) so the graph stays undirected."""
    n = S.shape[0]
    t = min(t, n)
    thresh = -jnp.sort(-S, axis=1)[:, t - 1][:, None]
    St = jnp.where(S >= thresh, S, 0.0)
    return jnp.maximum(St, St.T)


# ---------------------------------------------------------------------------
# Block-triangular schedule (the paper's i / n-i+1 pairing, block level)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class BlockSchedule:
    """Host-side static schedule for the triangular mode.

    n:        true number of points
    n_pad:    padded to a multiple of 2*m
    m:        number of devices (flattened mesh)
    b:        tile side = n_pad // (2m)
    perm:     (n_pad,) permuted-row -> original-row index map
    inv_perm: (n_pad,) original-row -> permuted-row
    table:    (m, 2m+1, 3) int32: [local sub-block (0/1), col block, is_diag]
    """

    n: int
    n_pad: int
    m: int
    b: int
    perm: np.ndarray
    inv_perm: np.ndarray
    table: np.ndarray


def make_schedule(n: int, m: int) -> BlockSchedule:
    n_pad = mesh_utils.pad_to_multiple(n, 2 * m)
    b = n_pad // (2 * m)
    # Block-interleave: device d owns original blocks {d, 2m-1-d} contiguously.
    block_of_dev = np.stack([np.arange(m), 2 * m - 1 - np.arange(m)], axis=1)  # (m, 2)
    perm_blocks = block_of_dev.reshape(-1)  # permuted block p -> original block
    perm = (perm_blocks[:, None] * b + np.arange(b)[None, :]).reshape(-1)
    inv_perm = np.argsort(perm)
    # orig block id of permuted block p
    orig_of_perm = perm_blocks
    # For each device: tiles (p_local, q) with orig(p) <= orig(q); q is a
    # *permuted* column block (columns live in permuted order too).
    rows_per_dev = []
    for d in range(m):
        entries = []
        for p_local in range(2):
            op = block_of_dev[d, p_local]
            for q in range(2 * m):
                oq = orig_of_perm[q]
                if op <= oq:
                    entries.append((p_local, q, 1 if op == oq else 0))
        assert len(entries) == 2 * m + 1, (d, len(entries))
        rows_per_dev.append(entries)
    table = np.asarray(rows_per_dev, dtype=np.int32)  # (m, 2m+1, 3)
    return BlockSchedule(n=n, n_pad=n_pad, m=m, b=b, perm=perm,
                         inv_perm=inv_perm, table=table)


@jax.tree_util.register_pytree_node_class
@dataclass
class UpperSim:
    """Row-sharded masked-upper similarity in block-permuted order."""

    U: jax.Array          # (n_pad, n_pad) row-sharded; zero below the schedule triangle
    diag: jax.Array       # (n_pad,) diagonal of S (1.0 on valid points, 0 on pad)
    schedule: Any         # BlockSchedule (static)
    mesh: Any             # Mesh (static)
    axis: str             # mesh axis name used for row sharding (flattened)

    def tree_flatten(self):
        return (self.U, self.diag), (self.schedule, self.mesh, self.axis)

    def tree_unflatten(aux, children):
        U, diag = children
        schedule, mesh, axis = aux
        return UpperSim(U=U, diag=diag, schedule=schedule, mesh=mesh, axis=axis)

    tree_unflatten = staticmethod(tree_unflatten)


def _row_axes(mesh: Mesh) -> tuple[str, ...]:
    return mesh_utils.flat_axes(mesh)


def similarity_upper_blocks(
    x: jax.Array,
    sigma: float | jax.Array,
    mesh: Mesh,
    schedule: BlockSchedule | None = None,
) -> UpperSim:
    """Paper-faithful phase 1: balanced triangular tile computation.

    ``x`` is (n, d) replicated (points are small next to the n x n matrix —
    same assumption as the paper storing them in an HBase table every worker
    reads).  Returns the permuted, row-sharded upper blocks.
    """
    axes = _row_axes(mesh)
    m = mesh_utils.mesh_size(mesh)
    sched = schedule or make_schedule(int(x.shape[0]), m)
    n, n_pad, b = sched.n, sched.n_pad, sched.b
    d_feat = x.shape[1]

    xp = jnp.zeros((n_pad, d_feat), x.dtype).at[: n].set(x)[sched.perm]
    table = jnp.asarray(sched.table)            # (m, 2m+1, 3)
    valid_perm = jnp.asarray((sched.perm < n))  # (n_pad,) bool, permuted order
    sigma = jnp.asarray(sigma, x.dtype)

    axis = axes[0] if len(axes) == 1 else axes  # shard_map spec entry
    n_tiles = 2 * m + 1

    def body(x_local, table_local, valid_local):
        # x_local: (2b, d) this device's two permuted blocks
        # table_local: (1, 2m+1, 3); valid_local: (2b,)
        x_full = lax.all_gather(x_local, axis, tiled=True)       # (n_pad, d)
        valid_full = lax.all_gather(valid_local, axis, tiled=True)
        tbl = table_local[0]

        def tile_step(t, U):
            p_local = tbl[t, 0]
            q = tbl[t, 1]
            is_diag = tbl[t, 2]
            rows = lax.dynamic_slice(x_local, (p_local * b, 0), (b, d_feat))
            cols = lax.dynamic_slice(x_full, (q * b, 0), (b, d_feat))
            tile = rbf_kernel(rows, cols, sigma)
            # diagonal tile: keep upper-inclusive only (pairs counted once)
            tri = jnp.triu(jnp.ones((b, b), tile.dtype))
            tile = jnp.where(is_diag > 0, tile * tri, tile)
            # padding mask
            rv = lax.dynamic_slice(valid_local, (p_local * b,), (b,))
            cv = lax.dynamic_slice(valid_full, (q * b,), (b,))
            tile = tile * rv[:, None].astype(tile.dtype) * cv[None, :].astype(tile.dtype)
            return lax.dynamic_update_slice(U, tile, (p_local * b, q * b))

        U_local = jnp.zeros((2 * b, n_pad), x.dtype)
        U_local = lax.pcast(U_local, tuple(axes), to="varying")  # mark carry device-varying
        U_local = lax.fori_loop(0, n_tiles, tile_step, U_local)
        return U_local

    shard = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axes, None), P(axes, None, None), P(axes)),
        out_specs=P(axes, None),
    )
    U = shard(xp, table, valid_perm)
    diag = valid_perm.astype(x.dtype)  # RBF diagonal is exp(0) = 1 on valid rows
    return UpperSim(U=U, diag=diag, schedule=sched, mesh=mesh, axis=axes)


def sym_matmat(upper: UpperSim, V: jax.Array) -> jax.Array:
    """S @ V without materializing the mirror:  SV = UV + UᵀV - diag*V.

    ``V`` replicated (n_pad, b), result replicated (n_pad, b).  One psum
    per call *regardless of the block width* — each device streams its
    row block of U once and amortizes it over all b columns, the matmat
    generalization of the paper's "move the vector to the data" MapReduce
    (with the transpose term folded in locally; Hadoop would store both
    triangles or shuffle twice).
    """
    sched: BlockSchedule = upper.schedule
    mesh = upper.mesh
    axes = upper.axis
    axis = axes[0] if len(axes) == 1 else axes
    b2 = 2 * sched.b
    width = int(V.shape[1])

    def body(U_local, diag_local, V_full):
        idx = lax.axis_index(axis)
        r0 = idx * b2
        V_rows = lax.dynamic_slice(V_full, (r0, 0), (b2, width))
        part = jnp.zeros_like(V_full)
        part = lax.dynamic_update_slice(part, matmul(U_local, V_full),
                                        (r0, 0))
        part = part + matmul(U_local.T, V_rows)
        part = part - lax.dynamic_update_slice(
            jnp.zeros_like(V_full), diag_local[:, None] * V_rows, (r0, 0))
        return lax.psum(part, axis)

    shard = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axes, None), P(axes), P()),
        out_specs=P(),
    )
    return shard(upper.U, upper.diag, V)


def sym_matvec(upper: UpperSim, v: jax.Array) -> jax.Array:
    """S @ v — the width-1 view of :func:`sym_matmat`."""
    return sym_matmat(upper, v[:, None])[:, 0]


def materialize(upper: UpperSim) -> jax.Array:
    """Full symmetric S (row-sharded, permuted order): U + Uᵀ - diag.

    The transpose of a row-sharded matrix is GSPMD's all-to-all — the direct
    analogue of the Hadoop shuffle that mirrors the triangle.
    """
    S = upper.U + upper.U.T - jnp.diag(upper.diag)
    axes = upper.axis
    return lax.with_sharding_constraint(
        S, NamedSharding(upper.mesh, P(axes, None)))


@jax.tree_util.register_pytree_node_class
@dataclass
class UpperSimCompact:
    """Triangular similarity stored as COMPACT per-device tile stacks
    (n_tiles, b, b) instead of the wide (2b, n_pad) row blocks.

    Perf iteration S1 (EXPERIMENTS.md §Perf): the wide layout pays a
    dynamic-update-slice into a 2b x n_pad buffer per tile — XLA
    materializes copies, ~100x the useful traffic.  The compact layout
    writes each tile once; sym_matvec reads each tile once and touches
    only two b-slices of the vector per tile.
    """

    tiles: jax.Array      # (m * (2m+1), b, b) sharded on dim 0
    diag: jax.Array       # (n_pad,) diagonal of S
    schedule: Any
    mesh: Any
    axis: Any

    def tree_flatten(self):
        return (self.tiles, self.diag), (self.schedule, self.mesh, self.axis)

    @staticmethod
    def tree_unflatten(aux, children):
        tiles, diag = children
        schedule, mesh, axis = aux
        return UpperSimCompact(tiles=tiles, diag=diag, schedule=schedule,
                               mesh=mesh, axis=axis)


def similarity_upper_blocks_compact(
    x: jax.Array,
    sigma: float | jax.Array,
    mesh: Mesh,
    schedule: BlockSchedule | None = None,
) -> UpperSimCompact:
    """Paper-faithful balanced triangular schedule, compact tile storage."""
    axes = _row_axes(mesh)
    m = mesh_utils.mesh_size(mesh)
    sched = schedule or make_schedule(int(x.shape[0]), m)
    n, n_pad, b = sched.n, sched.n_pad, sched.b
    d_feat = x.shape[1]

    xp = jnp.zeros((n_pad, d_feat), x.dtype).at[:n].set(x)[sched.perm]
    table = jnp.asarray(sched.table)
    valid_perm = jnp.asarray(sched.perm < n)
    sigma = jnp.asarray(sigma, x.dtype)
    axis = axes[0] if len(axes) == 1 else axes
    n_tiles = 2 * m + 1

    def body(x_local, table_local, valid_local):
        x_full = lax.all_gather(x_local, axis, tiled=True)
        valid_full = lax.all_gather(valid_local, axis, tiled=True)
        tbl = table_local[0]

        def one_tile(_, t):
            p_local, q, is_diag = tbl[t, 0], tbl[t, 1], tbl[t, 2]
            rows = lax.dynamic_slice(x_local, (p_local * b, 0), (b, d_feat))
            cols = lax.dynamic_slice(x_full, (q * b, 0), (b, d_feat))
            tile = rbf_kernel(rows, cols, sigma)
            tri = jnp.triu(jnp.ones((b, b), tile.dtype))
            tile = jnp.where(is_diag > 0, tile * tri, tile)
            rv = lax.dynamic_slice(valid_local, (p_local * b,), (b,))
            cv = lax.dynamic_slice(valid_full, (q * b,), (b,))
            return None, tile * rv[:, None].astype(tile.dtype) * cv[None, :].astype(tile.dtype)

        _, tiles = lax.scan(one_tile, None, jnp.arange(n_tiles))
        return tiles

    shard = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axes, None), P(axes, None, None), P(axes)),
        out_specs=P(axes, None, None),
    )
    tiles = shard(xp, table, valid_perm)
    return UpperSimCompact(tiles=tiles, diag=valid_perm.astype(x.dtype),
                           schedule=sched, mesh=mesh, axis=axes)


def sym_matmat_compact(upper: UpperSimCompact, V: jax.Array) -> jax.Array:
    """S @ V from compact tiles: each tile is read ONCE PER BLOCK (not
    once per vector); only two b-row slices of the block are touched per
    tile; one psum combines."""
    sched: BlockSchedule = upper.schedule
    axes = upper.axis
    axis = axes[0] if len(axes) == 1 else axes
    b = sched.b
    m = sched.m
    n_tiles = 2 * m + 1
    width = int(V.shape[1])

    def body(tiles_local, table_local, diag_local, V_full):
        idx = lax.axis_index(axis)
        dev_r0 = idx * 2 * b
        tbl = table_local[0]

        def one(t, partial):
            p_local, q = tbl[t, 0], tbl[t, 1]
            r0 = dev_r0 + p_local * b
            c0 = q * b
            tile = tiles_local[t]
            Vr = lax.dynamic_slice(V_full, (r0, 0), (b, width))
            Vc = lax.dynamic_slice(V_full, (c0, 0), (b, width))
            # rows += tile @ V[cols]
            cur = lax.dynamic_slice(partial, (r0, 0), (b, width))
            partial = lax.dynamic_update_slice(
                partial, cur + matmul(tile, Vc), (r0, 0))
            # cols += tile^T @ V[rows]  (the mirror, never materialized)
            cur = lax.dynamic_slice(partial, (c0, 0), (b, width))
            partial = lax.dynamic_update_slice(
                partial, cur + matmul(tile.T, Vr), (c0, 0))
            return partial

        partial = jnp.zeros_like(V_full)
        partial = lax.pcast(partial, tuple(axes), to="varying")
        partial = lax.fori_loop(0, n_tiles, one, partial)
        # diagonal tiles contribute their diagonal twice via the mirror
        Vr2 = lax.dynamic_slice(V_full, (dev_r0, 0), (2 * b, width))
        corr = lax.dynamic_update_slice(
            jnp.zeros_like(V_full), diag_local[:, None] * Vr2, (dev_r0, 0))
        return lax.psum(partial - corr, axis)

    shard = jax.shard_map(
        body, mesh=upper.mesh,
        in_specs=(P(axes, None, None), P(axes, None, None), P(axes), P()),
        out_specs=P(),
    )
    table = jnp.asarray(sched.table)
    return shard(upper.tiles, table, upper.diag, V)


def sym_matvec_compact(upper: UpperSimCompact, v: jax.Array) -> jax.Array:
    """S @ v — the width-1 view of :func:`sym_matmat_compact`."""
    return sym_matmat_compact(upper, v[:, None])[:, 0]


def materialize_compact(upper: UpperSimCompact) -> jax.Array:
    """Full symmetric S (permuted order) from the compact tile stacks.

    The schedule table is host-static, so this is a plain unrolled scatter —
    used by the exact-eigh backend, not by the iterative path.
    """
    sched: BlockSchedule = upper.schedule
    b, m = sched.b, sched.m
    n_tiles = 2 * m + 1
    U = jnp.zeros((sched.n_pad, sched.n_pad), upper.tiles.dtype)
    for d in range(m):
        for t, (p_local, q, _is_diag) in enumerate(sched.table[d]):
            r0 = d * 2 * b + int(p_local) * b
            c0 = int(q) * b
            U = U.at[r0:r0 + b, c0:c0 + b].set(upper.tiles[d * n_tiles + t])
    return U + U.T - jnp.diag(upper.diag)


def distributed_similarity_full(
    x: jax.Array, sigma: float | jax.Array, mesh: Mesh
) -> jax.Array:
    """Beyond-paper "full" mode: each device computes its whole row block.

    2x pair-FLOPs vs triangular, but no mirror/all-to-all and no permutation.
    Returns (n_pad, n_pad) row-sharded symmetric S in *original* order.
    """
    axes = _row_axes(mesh)
    m = mesh_utils.mesh_size(mesh)
    n = int(x.shape[0])
    n_pad = mesh_utils.pad_to_multiple(n, m)
    d_feat = x.shape[1]
    xp = jnp.zeros((n_pad, d_feat), x.dtype).at[:n].set(x)
    valid = (jnp.arange(n_pad) < n)
    sigma = jnp.asarray(sigma, x.dtype)
    axis = axes[0] if len(axes) == 1 else axes

    def body(x_local, valid_local):
        x_full = lax.all_gather(x_local, axis, tiled=True)
        valid_full = lax.all_gather(valid_local, axis, tiled=True)
        S_local = rbf_kernel(x_local, x_full, sigma)
        S_local = S_local * valid_local[:, None].astype(S_local.dtype)
        S_local = S_local * valid_full[None, :].astype(S_local.dtype)
        return S_local

    shard = jax.shard_map(
        body, mesh=mesh, in_specs=(P(axes, None), P(axes)), out_specs=P(axes, None)
    )
    return shard(xp, valid)


def unpermute_rows(values_perm: jax.Array, schedule: BlockSchedule) -> jax.Array:
    """Map a per-(permuted-)row vector back to original point order."""
    return values_perm[jnp.asarray(schedule.inv_perm)][: schedule.n]


def permute_rows(values: jax.Array, schedule: BlockSchedule) -> jax.Array:
    n_pad = schedule.n_pad
    padded = jnp.zeros((n_pad,) + values.shape[1:], values.dtype).at[: schedule.n].set(values)
    return padded[jnp.asarray(schedule.perm)]
