"""Pallas TPU kernel for the paper's phase-3 map function: fused
distance + argmin assignment.

One grid cell assigns a (bm,) row tile of points: distances to all k
centers are computed in VMEM ((bm, k) intermediate, never written to HBM)
and reduced to (argmin, min) — fusing the paper's per-point map loop into
one MXU matmul + VPU reduction per tile.  Centers (k, d) are small and
replicated to every cell (the paper's "center file").

Both outputs are written as (bm, 1) column blocks: Mosaic tiles a 1-D
(bm,) block in multiples of 1,024 elements, so a 1-D output refuses to
compile for bm < 1024, while a column block compiles at every legal bm.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.precision import mxu_precision


def _assign_kernel(p_ref, c_ref, idx_ref, dist_ref):
    p = p_ref[...]                    # (bm, d)
    c = c_ref[...]                    # (k, d)
    pp = jnp.sum(p * p, axis=-1)[:, None]
    cc = jnp.sum(c * c, axis=-1)[None, :]
    pc = jax.lax.dot_general(
        p, c, (((1,), (1,)), ((), ())), precision=mxu_precision(p.dtype),
        preferred_element_type=jnp.float32)
    d2 = jnp.maximum(pp + cc - 2.0 * pc, 0.0)          # (bm, k)
    idx_ref[...] = jnp.argmin(d2, axis=1, keepdims=True).astype(jnp.int32)
    dist_ref[...] = jnp.min(d2, axis=1, keepdims=True).astype(dist_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def kmeans_assign(points: jax.Array, centers: jax.Array,
                  *, bm: int = 512, interpret: bool = True
                  ) -> tuple[jax.Array, jax.Array]:
    """(labels int32 (n,), sq-dists (n,)); n must divide bm — see ops.py."""
    n, d = points.shape
    k = centers.shape[0]
    assert n % bm == 0, (n, bm)
    grid = (n // bm,)
    idx, dist = pl.pallas_call(
        _assign_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, d), lambda i: (i, 0)),
            pl.BlockSpec((k, d), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.int32),
            jax.ShapeDtypeStruct((n, 1), points.dtype),
        ],
        interpret=interpret,
    )(points, centers)
    return idx[:, 0], dist[:, 0]
