"""Flash-style fused RBF matmat: the matrix-free affinity hot loop.

One Pallas kernel computes

    O = diag(row_scale) . exp(-||x_i - y_j||^2 / 2 sigma^2) . diag(col_scale) @ V

without ever materializing the (n, m) similarity matrix: each grid cell
streams a (bm, d) row tile of ``x``, a (bn, d) column tile of ``y`` and the
matching (bn, b) tile of ``V`` into VMEM, builds the RBF tile *in register*
(squared distances via the ``|x|^2 + |y|^2 - 2 x.y`` MXU decomposition),
applies the D^{-1/2} normalization scales in place, and accumulates the
(bm, b) product directly into the output tile — the flash-attention
recompute trick applied to the spectral-clustering kernel matrix (Jin &
JaJa 2018: recomputing kernel tiles beats storing them once bandwidth is
the bottleneck).  Affinity memory drops from O(n^2) to O(n*d + n*b).

Mixed precision: ``compute_dtype`` selects the dtype the two MXU products
run in — bf16 operands double MXU throughput on TPU (the cast happens in
register, so HBM traffic is unchanged); the squared-norm terms, the exp,
and BOTH accumulations always stay in f32
(``preferred_element_type=jnp.float32``), so bf16 only perturbs the tile
entries, not the reduction.  A float32 product asks for HIGHEST
precision (``repro.precision.mxu_precision``).

Tile/grid conventions follow ``kernels/rbf_similarity`` (points short and
wide: feature dim kept whole in VMEM) and ``kernels/block_matmat`` (output
row tile revisited across the column grid dimension, initialized at
``j == 0`` and accumulated in place).

VMEM per cell (f32, bm=bn=128, d<=512, b<=64):
  x tile 256 KiB + y tile 256 KiB + V tile 32 KiB + RBF tile 64 KiB
  + out 32 KiB  << 16 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.block_matvec import check_tiles, interpret_default
from repro.precision import mxu_precision

# names accepted by the public ``compute_dtype`` knob (estimator kwarg /
# --compute-dtype CLI flag); None means full f32
_COMPUTE_DTYPES = {
    None: jnp.float32,
    "f32": jnp.float32, "float32": jnp.float32,
    "bf16": jnp.bfloat16, "bfloat16": jnp.bfloat16,
}


def resolve_compute_dtype(spec) -> jnp.dtype:
    """'bf16' | 'float32' | dtype | None -> the kernel compute dtype."""
    if isinstance(spec, str):
        try:
            return _COMPUTE_DTYPES[spec.lower()]
        except KeyError:
            raise ValueError(
                f"unknown compute_dtype {spec!r}; expected one of "
                f"{sorted(k for k in _COMPUTE_DTYPES if k)}") from None
    if spec is None:
        return jnp.float32
    dt = jnp.dtype(spec)
    if dt not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        raise ValueError(f"compute_dtype must be float32 or bfloat16, "
                         f"got {dt}")
    return jnp.bfloat16 if dt == jnp.dtype(jnp.bfloat16) else jnp.float32


def default_tile(n: int) -> int:
    """MXU-aligned tile side for the fused kernels (fit- and serving-side
    share one rule): larger tiles quarter the grid-cell count — which is
    what interpret mode pays for — and on TPU amortize more MXU work per
    VMEM fill; small problems stay at 128 so padding overhead stays
    bounded."""
    return 256 if n >= 2048 else 128


def _fused_tile_product(x_ref, y_ref, v_ref, cs_ref, inv2s2_ref,
                        *, compute_dtype):
    """Shared tile body: the in-register RBF tile times the scaled V tile
    — the algorithm; where the (bm, b) partial sum then accumulates is the
    schedule's business (inplace vs scratch kernel variants below)."""
    x = x_ref[...]                              # (bm, d) f32
    y = y_ref[...]                              # (bn, d) f32
    # squared norms in f32 (cheap VPU work; keeping them full precision
    # makes bf16 perturb only the cross term, not the distance scale)
    xx = jnp.sum(x * x, axis=-1)[:, None]
    yy = jnp.sum(y * y, axis=-1)[None, :]
    precision = mxu_precision(compute_dtype)
    xy = jax.lax.dot_general(
        x.astype(compute_dtype), y.astype(compute_dtype),
        (((1,), (1,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32)     # MXU, f32 accumulate
    d2 = jnp.maximum(xx + yy - 2.0 * xy, 0.0)
    tile = jnp.exp(-d2 * inv2s2_ref[0])         # RBF tile, in-register only
    w = cs_ref[...] * v_ref[...]                # (bn, b): D^{-1/2} V tile
    acc = jax.lax.dot_general(
        tile.astype(compute_dtype), w.astype(compute_dtype),
        (((1,), (0,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32)     # (bm, b), f32 accumulate
    return tile, acc


def _fused_kernel(x_ref, y_ref, v_ref, rs_ref, cs_ref, inv2s2_ref, o_ref,
                  *, compute_dtype):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    _, acc = _fused_tile_product(x_ref, y_ref, v_ref, cs_ref, inv2s2_ref,
                                 compute_dtype=compute_dtype)
    o_ref[...] += rs_ref[...] * acc             # row D^{-1/2}, in place


def _fused_kernel_scratch(x_ref, y_ref, v_ref, rs_ref, cs_ref, inv2s2_ref,
                          o_ref, acc_ref, *, compute_dtype):
    """acc='scratch' schedule variant: partial sums live in an f32 VMEM
    scratch tile; the output tile is written once, at the last column
    step, instead of being read-modified-written per step."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _, acc = _fused_tile_product(x_ref, y_ref, v_ref, cs_ref, inv2s2_ref,
                                 compute_dtype=compute_dtype)
    acc_ref[...] += acc

    @pl.when(j == pl.num_programs(1) - 1)
    def _flush():
        o_ref[...] = rs_ref[...] * acc_ref[...]


def _nystrom_kernel(x_ref, y_ref, v_ref, cs_ref, cv_ref, inv2s2_ref,
                    o_ref, deg_ref, *, compute_dtype):
    """Rectangular serving twin of :func:`_fused_kernel`: one sweep over the
    training tiles accumulates BOTH the product ``K @ (col_scale * V)`` and
    the query-side degree column ``K @ col_valid`` — the two quantities the
    Nystrom out-of-sample extension needs, so ``transform`` costs exactly
    one pass over the training set per query batch."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        deg_ref[...] = jnp.zeros_like(deg_ref)

    tile, acc = _fused_tile_product(x_ref, y_ref, v_ref, cs_ref, inv2s2_ref,
                                    compute_dtype=compute_dtype)
    # degree counts every VALID training column (padding masked by cv);
    # the product is masked through col_scale (0 on padding) instead, so
    # isolated training points (valid but zero-degree) still contribute to
    # the query degree exactly like the materialized dense path
    deg_ref[...] += jnp.sum(tile * cv_ref[...][:, 0][None, :], axis=1,
                            keepdims=True)
    o_ref[...] += acc


def _nystrom_kernel_scratch(x_ref, y_ref, v_ref, cs_ref, cv_ref, inv2s2_ref,
                            o_ref, deg_ref, acc_ref, dacc_ref,
                            *, compute_dtype):
    """acc='scratch' variant of :func:`_nystrom_kernel`: both running sums
    (product and degree) live in VMEM scratch; one output write each at
    the last training-tile step."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        dacc_ref[...] = jnp.zeros_like(dacc_ref)

    tile, acc = _fused_tile_product(x_ref, y_ref, v_ref, cs_ref, inv2s2_ref,
                                    compute_dtype=compute_dtype)
    dacc_ref[...] += jnp.sum(tile * cv_ref[...][:, 0][None, :], axis=1,
                             keepdims=True)
    acc_ref[...] += acc

    @pl.when(j == pl.num_programs(1) - 1)
    def _flush():
        o_ref[...] = acc_ref[...]
        deg_ref[...] = dacc_ref[...]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "compute_dtype",
                                             "acc", "interpret"))
def _nystrom(x, y, V, inv2s2, col_scale, col_valid, *, bm, bn, compute_dtype,
             acc, interpret):
    from jax.experimental.pallas import tpu as pltpu
    m, d = x.shape                               # m queries vs n training
    n = y.shape[0]
    b = V.shape[1]
    grid = (m // bm, n // bn)
    body = _nystrom_kernel if acc == "inplace" else _nystrom_kernel_scratch
    scratch = [] if acc == "inplace" else \
        [pltpu.VMEM((bm, b), jnp.float32), pltpu.VMEM((bm, 1), jnp.float32)]
    kernel = functools.partial(body, compute_dtype=compute_dtype)
    return pl.pallas_call(
        kernel,
        grid=grid,
        scratch_shapes=scratch,
        in_specs=[
            pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
            pl.BlockSpec((bn, b), lambda i, j: (j, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((1,), lambda i, j: (0,)),  # 1/(2 sigma^2)
        ],
        out_specs=[
            pl.BlockSpec((bm, b), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((m, b), jnp.float32),
                   jax.ShapeDtypeStruct((m, 1), jnp.float32)],
        interpret=interpret,
    )(x, y, V, col_scale, col_valid, inv2s2)


def fused_nystrom_matmat(x: jax.Array, y: jax.Array, V: jax.Array, sigma,
                         col_scale: jax.Array, col_valid: jax.Array,
                         *, bm: int = 128, bn: int = 128,
                         compute_dtype=None, acc: str = "inplace",
                         interpret: bool | None = None
                         ) -> tuple[jax.Array, jax.Array]:
    """One fused pass of the Nystrom out-of-sample extension.

    Returns ``(K @ (col_scale * V), K @ col_valid)`` for the RBF kernel
    ``K = RBF(x, y; sigma)`` — the unnormalized embedding product and the
    query degree column, computed from the same in-register kernel tiles
    (the similarity never exists).  ``x`` (m, d) queries, ``y`` (n, d)
    training points, ``V`` (n, b); m, n must divide the (bm, bn) tiles —
    ``ops.fused_nystrom_matmat`` is the padded public entry point.  Both
    outputs are f32 regardless of ``compute_dtype``."""
    if interpret is None:
        interpret = interpret_default()
    check_tiles(bm, bn, interpret=bool(interpret),
                kernel="fused_nystrom_matmat")
    m, d = x.shape                               # m queries vs n training
    n = y.shape[0]
    assert V.ndim == 2 and V.shape[0] == n, (x.shape, y.shape, V.shape)
    assert m % bm == 0 and n % bn == 0, (m, n, bm, bn)
    cdtype = resolve_compute_dtype(compute_dtype)
    inv2s2 = (1.0 / (2.0 * jnp.asarray(sigma, jnp.float32) ** 2)).reshape(1)
    return _nystrom(jnp.asarray(x, jnp.float32), jnp.asarray(y, jnp.float32),
                    jnp.asarray(V, jnp.float32), inv2s2,
                    jnp.asarray(col_scale, jnp.float32).reshape(n, 1),
                    jnp.asarray(col_valid, jnp.float32).reshape(n, 1),
                    bm=bm, bn=bn, compute_dtype=cdtype, acc=acc,
                    interpret=bool(interpret))


@functools.partial(jax.jit, static_argnames=("bm", "bn", "compute_dtype",
                                             "acc", "interpret"))
def _fused(x, y, V, inv2s2, row_scale, col_scale, *, bm, bn, compute_dtype,
           acc, interpret):
    from jax.experimental.pallas import tpu as pltpu
    n, d = x.shape
    m = y.shape[0]
    b = V.shape[1]
    grid = (n // bm, m // bn)
    body = _fused_kernel if acc == "inplace" else _fused_kernel_scratch
    scratch = [] if acc == "inplace" else \
        [pltpu.VMEM((bm, b), jnp.float32)]
    kernel = functools.partial(body, compute_dtype=compute_dtype)
    return pl.pallas_call(
        kernel,
        grid=grid,
        scratch_shapes=scratch,
        in_specs=[
            pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
            pl.BlockSpec((bn, b), lambda i, j: (j, 0)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((1,), lambda i, j: (0,)),  # 1/(2 sigma^2)
        ],
        out_specs=pl.BlockSpec((bm, b), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, b), jnp.float32),
        interpret=interpret,
    )(x, y, V, row_scale, col_scale, inv2s2)


def fused_rbf_matmat(x: jax.Array, y: jax.Array, V: jax.Array, sigma,
                     row_scale: jax.Array, col_scale: jax.Array,
                     *, bm: int = 128, bn: int = 128,
                     compute_dtype=None, acc: str = "inplace",
                     interpret: bool | None = None) -> jax.Array:
    """diag(row_scale) @ RBF(x, y; sigma) @ diag(col_scale) @ V, fused.

    ``x`` (n, d), ``y`` (m, d), ``V`` (m, b), scales (n,)/(m,); n, m must
    divide the (bm, bn) tiles — ``ops.fused_rbf_matmat`` is the padded
    public entry point.  Output is (n, b) f32 regardless of
    ``compute_dtype`` (accumulation is always f32)."""
    if interpret is None:
        interpret = interpret_default()
    check_tiles(bm, bn, interpret=bool(interpret), kernel="fused_rbf_matmat")
    n, d = x.shape
    m = y.shape[0]
    assert V.ndim == 2 and V.shape[0] == m, (x.shape, y.shape, V.shape)
    assert n % bm == 0 and m % bn == 0, (n, m, bm, bn)
    cdtype = resolve_compute_dtype(compute_dtype)
    inv2s2 = (1.0 / (2.0 * jnp.asarray(sigma, jnp.float32) ** 2)).reshape(1)
    return _fused(jnp.asarray(x, jnp.float32), jnp.asarray(y, jnp.float32),
                  jnp.asarray(V, jnp.float32), inv2s2,
                  jnp.asarray(row_scale, jnp.float32).reshape(n, 1),
                  jnp.asarray(col_scale, jnp.float32).reshape(m, 1),
                  bm=bm, bn=bn, compute_dtype=cdtype, acc=acc,
                  interpret=bool(interpret))


def pass_bytes(n: int, m: int, d: int, b: int,
               *, bm: int = 128, bn: int = 128) -> int:
    """HBM->VMEM traffic model of ONE fused pass (the ``bytes_streamed``
    accounting unit the operator advertises): every (i, j) grid cell loads
    its x/y point tiles, V tile and scale columns; the output row tile is
    written once per row stripe.  Compare against the materialized path's
    n*m*4 bytes per pass to see the recompute-vs-store trade.

    Everything is billed at f32: the points live in HBM as f32 and the
    bf16 ``compute_dtype`` cast happens *in register*, after the load —
    it halves MXU operand volume, not HBM traffic (storing the points in
    bf16 would be the traffic lever, and would also perturb the norms)."""
    cells = (n // bm) * (m // bn)
    per_cell = (bm * d + bn * d) * 4 + (bn * b + bm + bn) * 4
    return cells * per_cell + (n // bm) * bm * b * 4
