"""Pallas TPU kernels for the eigensolver hot spot: row-blocked mat-vec and
its multi-vector generalization, the row-blocked **mat-mat** (paper §4.3.2).

Grid = (row tiles, col tiles); the output row tile is revisited across the
column dimension and accumulated in place (initialized at j == 0), so the
matrix streams HBM->VMEM once while the vector/block tile stays resident —
the TPU translation of the paper's "move the vector to the data, not the
data".

``block_matmat`` is the canonical kernel: an MXU-shaped
``(bm, bn) @ (bn, b)`` tile product per grid step, amortizing each sweep of
``A`` over all ``b`` columns of ``V`` at once (one matrix pass per block
instead of one per vector).  ``block_matvec`` is its width-1 view.

``interpret`` defaults to auto-detection from ``jax.default_backend()``:
compiled on TPU, interpreter elsewhere — so real TPU runs never silently
take the interpreter path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.precision import mxu_precision


def interpret_default() -> bool:
    """Interpret only off-TPU (CPU/GPU run the kernel body in Python for
    correctness; TPU compiles it)."""
    return jax.default_backend() != "tpu"


def check_tiles(bm: int, bn: int, *, interpret: bool = False,
                kernel: str = "block_matmat") -> None:
    """Reject illegal tile edges with a one-line error instead of a Pallas
    lowering failure: ``bm``/``bn`` must be positive multiples of the f32
    sublane count (8); the reduction tile ``bn`` (the lane dimension of
    the A tile) must additionally be a multiple of the 128 lane width on
    the compiled path (interpret mode relaxes it, so small-tile tests can
    exercise multi-tile grids on small inputs)."""
    for name, v in (("bm", bm), ("bn", bn)):
        if v <= 0 or v % 8:
            raise ValueError(
                f"{kernel}: tile {name}={v} must be a positive multiple of "
                f"8 (the f32 sublane count)")
    if not interpret and bn % 128:
        raise ValueError(
            f"{kernel}: tile bn={bn} must be a multiple of 128 (the TPU "
            f"lane width) for the compiled path; pick bn from "
            f"{{128, 256, 512, ...}} or pass interpret=True")


def _matmat_kernel(a_ref, v_ref, o_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a = a_ref[...]                       # (bm, bn)
    v = v_ref[...]                       # (bn, b)
    acc = jax.lax.dot_general(
        a, v, (((1,), (0,)), ((), ())), precision=mxu_precision(a.dtype),
        preferred_element_type=jnp.float32)  # (bm, b)
    o_ref[...] += acc.astype(o_ref.dtype)


def _matmat_kernel_scratch(a_ref, v_ref, o_ref, acc_ref):
    """acc='scratch' variant: the running sum lives in an f32 VMEM scratch
    tile and the output is written ONCE, at the last reduction step — the
    revisited output tile is never read back."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], v_ref[...], (((1,), (0,)), ((), ())),
        precision=mxu_precision(a_ref.dtype),
        preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(1) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "acc", "interpret"))
def _matmat(A: jax.Array, V: jax.Array, *, bm: int, bn: int, acc: str,
            interpret: bool) -> jax.Array:
    from jax.experimental.pallas import tpu as pltpu
    n, m = A.shape
    b = V.shape[1]
    grid = (n // bm, m // bn)
    kernel = _matmat_kernel if acc == "inplace" else _matmat_kernel_scratch
    scratch = [] if acc == "inplace" else [pltpu.VMEM((bm, b), jnp.float32)]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bn, b), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, b), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, b), jnp.float32),
        scratch_shapes=scratch,
        interpret=interpret,
    )(A, V)


def block_matmat(A: jax.Array, V: jax.Array, *, bm: int = 256, bn: int = 512,
                 acc: str = "inplace",
                 interpret: bool | None = None) -> jax.Array:
    """A @ V with (bm, bn) VMEM tiles; A (n, m), V (m, b); shapes must
    divide the tiles — see ops.py for the padding wrapper."""
    if interpret is None:
        interpret = interpret_default()
    check_tiles(bm, bn, interpret=bool(interpret))
    if acc not in ("inplace", "scratch"):
        raise ValueError(f"block_matmat: acc must be 'inplace' or "
                         f"'scratch', got {acc!r}")
    n, m = A.shape
    assert V.ndim == 2 and V.shape[0] == m, (A.shape, V.shape)
    assert n % bm == 0 and m % bn == 0, (n, m, bm, bn)
    out = _matmat(A, V, bm=bm, bn=bn, acc=acc, interpret=bool(interpret))
    return out.astype(V.dtype)


def block_matvec(A: jax.Array, v: jax.Array, *, bm: int = 256, bn: int = 512,
                 acc: str = "inplace",
                 interpret: bool | None = None) -> jax.Array:
    """A @ v — the width-1 view of :func:`block_matmat` (the vector is
    reshaped to (m, 1) so the product is an MXU ``dot``, not a VPU
    reduction)."""
    n, m = A.shape
    out = block_matmat(A, v.reshape(m, 1), bm=bm, bn=bn, acc=acc,
                       interpret=interpret)
    return out.reshape(n)
