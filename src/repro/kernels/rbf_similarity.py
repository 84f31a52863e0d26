"""Pallas TPU kernel for the paper's phase-1 hot spot: tiled RBF similarity.

One grid cell computes a (bm, bn) output tile from a (bm, d) row tile and a
(bn, d) column tile held in VMEM.  The squared distance uses the
``|x|^2 + |y|^2 - 2 x.y`` decomposition so the inner product runs on the MXU;
bm/bn default to 128/128 (MXU-aligned), and the feature dim is kept whole in
VMEM (spectral-clustering inputs are short-and-wide: n >> d).

VMEM budget per cell (f32, defaults, d<=512):
  x tile 128*512*4 = 256 KiB, y tile 256 KiB, out 64 KiB  << 16 MiB VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.precision import mxu_precision


def _rbf_kernel(x_ref, y_ref, inv2s2_ref, o_ref):
    x = x_ref[...]                    # (bm, d)
    y = y_ref[...]                    # (bn, d)
    xx = jnp.sum(x * x, axis=-1)[:, None]
    yy = jnp.sum(y * y, axis=-1)[None, :]
    xy = jax.lax.dot_general(
        x, y, (((1,), (1,)), ((), ())), precision=mxu_precision(x.dtype),
        preferred_element_type=jnp.float32)   # MXU matmul, f32 accumulate
    d2 = jnp.maximum(xx + yy - 2.0 * xy, 0.0)
    o_ref[...] = jnp.exp(-d2 * inv2s2_ref[0]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "grid_order", "interpret"))
def rbf_similarity(x: jax.Array, y: jax.Array, sigma,
                   *, bm: int = 128, bn: int = 128,
                   grid_order: str = "row-major",
                   interpret: bool = True) -> jax.Array:
    """Tiled RBF similarity; shapes must be multiples of (bm, bn) — use
    ``ops.rbf_similarity`` for the padded public entry point.

    ``grid_order`` is a schedule knob: "row-major" sweeps column tiles
    fastest (the x row tile stays resident across the row stripe),
    "col-major" sweeps row tiles fastest (the y tile stays resident) —
    legal here because every output tile is written exactly once, so the
    traversal order is free."""
    n, d = x.shape
    m = y.shape[0]
    assert n % bm == 0 and m % bn == 0, (n, m, bm, bn)
    assert grid_order in ("row-major", "col-major"), grid_order
    inv2s2 = (1.0 / (2.0 * jnp.asarray(sigma, jnp.float32) ** 2)).reshape(1)
    if grid_order == "row-major":
        grid = (n // bm, m // bn)
        row = lambda i, j: (i, j)               # noqa: E731
    else:                                        # grid dims swapped: row
        grid = (m // bn, n // bm)                # tile index is the LAST
        row = lambda j, i: (i, j)               # noqa: E731 - grid arg
    return pl.pallas_call(
        _rbf_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, d), lambda *ij: (row(*ij)[0], 0)),
            pl.BlockSpec((bn, d), lambda *ij: (row(*ij)[1], 0)),
            pl.BlockSpec((1,), lambda *ij: (0,)),  # 1/(2 sigma^2), replicated
        ],
        out_specs=pl.BlockSpec((bm, bn), row),
        out_shape=jax.ShapeDtypeStruct((n, m), x.dtype),
        interpret=interpret,
    )(x, y, inv2s2)
