"""Flash-style fused RBF matmat: the matrix-free affinity hot loop.

One Pallas kernel computes

    O = diag(row_scale) . exp(-||x_i - y_j||^2 / 2 sigma^2) . diag(col_scale) @ V

without ever materializing the (n, m) similarity matrix.  The grid is
(row tiles, column tiles, feature tiles): each step streams a (bm, bd)
tile of ``x`` and a (bn, bd) tile of ``y`` into VMEM and adds their
cross products ``x . y^T`` to an f32 (bm, bn) scratch tile.  At the last
feature step the RBF tile is built in register from the squared norms
(passed in once, f32) and that Gram tile (``|x|^2 + |y|^2 - 2 x.y``), the
D^{-1/2} scales are applied in place, and the (bm, b) product with the V
tile accumulates into the output tile — the flash-attention recompute
trick applied to the spectral-clustering kernel matrix (Jin & JaJa 2018:
recomputing kernel tiles beats storing them once bandwidth is the
bottleneck).  Affinity memory drops from O(n^2) to O(n*d + n*b), and the
feature tile ``bd`` bounds VMEM whatever the width d.

Precision follows the rows:

* bfloat16 rows go to the MXU as they are.  A bf16 x bf16 product is
  exact in f32, so the Gram tile is exact up to f32 accumulation; the exp
  and the tile . V product stay f32 (HIGHEST), because the cluster
  eigenvalues of the normalized operator sit near 4e-3 against its norm
  of 1 and a bf16 tile would blur them.  ``compute_dtype`` is not read.
* float32 rows run both products in ``compute_dtype``: float32 asks for
  HIGHEST (``repro.precision.mxu_precision``); bfloat16 rounds the
  operands of both products in register (HBM traffic is unchanged) and
  keeps f32 accumulation.

The output row tile is revisited across the column and feature grid
dimensions: initialized at the first (j, k) step and accumulated in place
(``acc="inplace"``), or held in an f32 scratch and written once at the
last step (``acc="scratch"``).  The Nystrom serving twin below keeps the
whole feature dimension in VMEM (its queries are few and its widths
those of the fit's points); it has no feature grid axis.

VMEM per grid step (``tune.schedule._fused_vmem``, inputs and output
double-buffered): at bm=bn=256, bd=d=8, b=8 in f32 about 0.6 MB; at
bm=bn=512 with 2 KiB feature tiles (bd=1024 in bf16, 512 in f32) and
b=64 about 6.8 MB, under the 8 MiB budget at any d.
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


# widest feature tile, in bytes of one row: wider rows are split into
# about equal tiles of at most this size, so the VMEM working set no longer
# grows with d (1,024 bf16 or 512 f32 columns)
MAX_D_TILE_BYTES = 2048


def _max_d_tile(itemsize: int) -> int:
    return MAX_D_TILE_BYTES // itemsize


def default_tile(n: int, d: int = 0, itemsize: int = 4) -> int:
    """MXU-aligned tile side for the fused kernels (fit- and serving-side
    share one rule): larger tiles quarter the grid-cell count — which is
    what interpret mode pays for — and on TPU amortize more MXU work per
    VMEM fill; small problems stay at 128 so padding overhead stays
    bounded.  Rows split into several feature tiles take 512: every
    feature step then loads both point tiles again, and at 512 a step's
    MXU work (2 bm bn bd flop) matches its (bm + bn) bd bf16 loads at the
    v5e's ratio of peak flop/s to HBM bytes/s (about 240)."""
    if n < 2048:
        return 128
    return 512 if d > _max_d_tile(itemsize) else 256


def default_d_tile(d: int, itemsize: int = 4) -> int:
    """Feature tile for rows of width ``d``: the whole row up to
    :data:`MAX_D_TILE_BYTES`, else the fewest tiles of equal 128-multiple
    width under it (the last may be padded with zero columns)."""
    widest = _max_d_tile(itemsize)
    if d <= widest:
        return d
    tiles = -(-d // widest)
    return -(-d // (tiles * 128)) * 128


def padded_width(d: int, bd: int) -> int:
    """``d`` rounded up to whole feature tiles (zero columns change
    neither distances nor norms)."""
    return -(-d // bd) * bd


def row_dtype(x) -> jnp.dtype:
    """The dtype the fused kernel keeps rows in: bfloat16 rows stay
    bfloat16, anything else is float32."""
    return (jnp.dtype(jnp.bfloat16) if jnp.dtype(x.dtype) == jnp.bfloat16
            else jnp.dtype(jnp.float32))


def _gram_tile(x, y, dtype):
    """(bm, bd) x (bn, bd)^T cross products in ``dtype``, f32 accumulate
    (bf16 operands: exact products)."""
    return jax.lax.dot_general(
        x.astype(dtype), y.astype(dtype), (((1,), (1,)), ((), ())),
        precision=mxu_precision(dtype), preferred_element_type=jnp.float32)


def _rbf_tile(xx, yy, xy, inv2s2):
    """exp(-(|x|^2 + |y|^2 - 2 x.y) / 2 sigma^2), in f32."""
    d2 = jnp.maximum(xx + yy - 2.0 * xy, 0.0)
    return jnp.exp(-d2 * inv2s2)


def _tile_times(tile, w, dtype):
    """(bm, bn) RBF tile times the (bn, b) scaled V tile, f32 accumulate."""
    return jax.lax.dot_general(
        tile.astype(dtype), w.astype(dtype), (((1,), (0,)), ((), ())),
        precision=mxu_precision(dtype), preferred_element_type=jnp.float32)


def _fused_tile_product(x_ref, y_ref, v_ref, cs_ref, inv2s2_ref,
                        *, compute_dtype):
    """Whole-feature tile body of the Nystrom twin: the in-register RBF
    tile and its product with the scaled V tile."""
    x = x_ref[...].astype(jnp.float32)          # (bm, d)
    y = y_ref[...].astype(jnp.float32)          # (bn, d)
    # squared norms in f32 (cheap VPU work; keeping them full precision
    # makes bf16 perturb only the cross term, not the distance scale)
    xx = jnp.sum(x * x, axis=-1)[:, None]
    yy = jnp.sum(y * y, axis=-1)[None, :]
    tile = _rbf_tile(xx, yy, _gram_tile(x, y, compute_dtype),
                     inv2s2_ref[0])
    w = cs_ref[...] * v_ref[...]                # (bn, b): D^{-1/2} V tile
    return tile, _tile_times(tile, w, compute_dtype)


def _accumulate_gram(x_ref, y_ref, g_ref, gram_dtype):
    """Add this feature tile's cross products to the (bm, bn) f32 Gram
    scratch, zeroed at the first feature step."""
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        g_ref[...] = jnp.zeros_like(g_ref)

    g_ref[...] += _gram_tile(x_ref[...], y_ref[...], gram_dtype)


def _tile_product(xn_ref, yn_ref, v_ref, cs_ref, inv2s2_ref, g_ref,
                  tile_dtype):
    """The finished Gram tile -> RBF tile (in register only) times the
    D^{-1/2}-scaled V tile: the (bm, b) partial product."""
    tile = _rbf_tile(xn_ref[...], yn_ref[...], g_ref[...], inv2s2_ref[0])
    return _tile_times(tile, cs_ref[...] * v_ref[...], tile_dtype)


def _fused_kernel(x_ref, y_ref, v_ref, xn_ref, yn_ref, rs_ref, cs_ref,
                  inv2s2_ref, o_ref, g_ref, *, gram_dtype, tile_dtype):
    j, k = pl.program_id(1), pl.program_id(2)

    @pl.when((j == 0) & (k == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    _accumulate_gram(x_ref, y_ref, g_ref, gram_dtype)

    @pl.when(k == pl.num_programs(2) - 1)
    def _finish():
        o_ref[...] += rs_ref[...] * _tile_product(   # row D^{-1/2}, in place
            xn_ref, yn_ref, v_ref, cs_ref, inv2s2_ref, g_ref, tile_dtype)


def _fused_kernel_scratch(x_ref, y_ref, v_ref, xn_ref, yn_ref, rs_ref,
                          cs_ref, inv2s2_ref, o_ref, g_ref, acc_ref,
                          *, gram_dtype, tile_dtype):
    """acc='scratch' schedule variant: partial sums live in an f32 VMEM
    scratch tile; the output tile is written once, at the last step,
    instead of being read-modified-written per column tile."""
    j, k = pl.program_id(1), pl.program_id(2)
    last_k = k == pl.num_programs(2) - 1

    @pl.when((j == 0) & (k == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _accumulate_gram(x_ref, y_ref, g_ref, gram_dtype)

    @pl.when(last_k)
    def _finish():
        acc_ref[...] += _tile_product(xn_ref, yn_ref, v_ref, cs_ref,
                                      inv2s2_ref, g_ref, tile_dtype)

    @pl.when(last_k & (j == pl.num_programs(1) - 1))
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


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bd", "gram_dtype",
                                             "tile_dtype", "acc",
                                             "interpret"))
def _fused(x, y, V, inv2s2, row_scale, col_scale, *, bm, bn, bd, gram_dtype,
           tile_dtype, acc, interpret):
    from jax.experimental.pallas import tpu as pltpu
    n, d = x.shape
    m = y.shape[0]
    b = V.shape[1]
    f32 = jnp.float32
    # squared norms once per pass, f32, from the rows as stored
    xn = jnp.sum(jnp.square(x.astype(f32)), axis=1).reshape(n, 1)
    yn = jnp.sum(jnp.square(y.astype(f32)), axis=1).reshape(1, m)
    grid = (n // bm, m // bn, d // bd)
    body = _fused_kernel if acc == "inplace" else _fused_kernel_scratch
    scratch = [pltpu.VMEM((bm, bn), f32)]                # the Gram tile
    if acc != "inplace":
        scratch.append(pltpu.VMEM((bm, b), f32))
    kernel = functools.partial(body, gram_dtype=gram_dtype,
                               tile_dtype=tile_dtype)
    return pl.pallas_call(
        kernel,
        grid=grid,
        scratch_shapes=scratch,
        in_specs=[
            pl.BlockSpec((bm, bd), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bd), lambda i, j, k: (j, k)),
            pl.BlockSpec((bn, b), lambda i, j, k: (j, 0)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),   # |x|^2
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),   # |y|^2
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j, k: (j, 0)),
            pl.BlockSpec((1,), lambda i, j, k: (0,)),  # 1/(2 sigma^2)
        ],
        out_specs=pl.BlockSpec((bm, b), lambda i, j, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, b), f32),
        interpret=interpret,
        name="_fused_kernel",
    )(x, y, V, xn, yn, row_scale, col_scale, inv2s2)


def fused_rbf_matmat(x: jax.Array, y: jax.Array, V: jax.Array, sigma,
                     row_scale: jax.Array, col_scale: jax.Array,
                     *, bm: int = 128, bn: int = 128, bd: int | None = None,
                     compute_dtype=None, acc: str = "inplace",
                     interpret: bool | None = None) -> jax.Array:
    """diag(row_scale) @ RBF(x, y; sigma) @ diag(col_scale) @ V, fused.

    ``x`` (n, d), ``y`` (m, d) in float32 or bfloat16 (see the module
    docstring for how each is multiplied), ``V`` (m, b), scales
    (n,)/(m,); n, m must divide the (bm, bn) tiles and d the feature tile
    ``bd`` (None: the whole row) -- ``ops.fused_rbf_matmat`` is the
    padded public entry point.  Output is (n, b) f32 regardless of the
    dtypes (accumulation is always f32)."""
    if interpret is None:
        interpret = interpret_default()
    check_tiles(bm, bn, interpret=bool(interpret), kernel="fused_rbf_matmat")
    n, d = x.shape
    m = y.shape[0]
    bd = d if bd is None else int(bd)
    assert V.ndim == 2 and V.shape[0] == m, (x.shape, y.shape, V.shape)
    assert n % bm == 0 and m % bn == 0 and d % bd == 0, (n, m, d, bm, bn, bd)
    rows = row_dtype(x) if row_dtype(x) == row_dtype(y) \
        else jnp.dtype(jnp.float32)
    if rows == jnp.bfloat16:        # exact Gram, f32 tile product
        gram_dtype, tile_dtype = jnp.bfloat16, jnp.float32
    else:
        gram_dtype = tile_dtype = resolve_compute_dtype(compute_dtype)
    inv2s2 = (1.0 / (2.0 * jnp.asarray(sigma, jnp.float32) ** 2)).reshape(1)
    return _fused(jnp.asarray(x, rows), jnp.asarray(y, rows),
                  jnp.asarray(V, jnp.float32), inv2s2,
                  jnp.asarray(row_scale, jnp.float32).reshape(n, 1),
                  jnp.asarray(col_scale, jnp.float32).reshape(m, 1),
                  bm=bm, bn=bn, bd=bd, gram_dtype=jnp.dtype(gram_dtype),
                  tile_dtype=jnp.dtype(tile_dtype), acc=acc,
                  interpret=bool(interpret))


def pass_bytes(n: int, m: int, d: int, b: int, *, bm: int = 128,
               bn: int = 128, bd: int | None = None,
               itemsize: int = 4) -> int:
    """HBM->VMEM traffic model of ONE fused pass (the ``bytes_streamed``
    accounting unit the operator advertises): every grid step loads its
    x/y point tiles at the rows' ``itemsize``; every (i, j) cell its V
    tile and scale and norm columns; the output row tile is written once
    per row stripe.  With one feature tile (``bd`` None or d) the x tile
    stays resident across a row stripe and only the y tiles stream.
    Compare against the materialized path's n*m*4 bytes per pass to see
    the recompute-vs-store trade."""
    bd = d if bd is None else bd
    rows, cols = n // bm, m // bn
    if bd >= d:
        points = rows * bm * d + rows * cols * bn * d
    else:
        points = rows * cols * (bm + bn) * d
    per_cell = (bn * b + 2 * bm + 2 * bn) * 4
    return points * itemsize + rows * cols * per_cell + n * b * 4
