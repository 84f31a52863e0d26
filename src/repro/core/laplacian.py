"""Normalized Laplacian operators (paper §3.2.2 / Alg. 4.1 steps 2-3).

L_sym = I - D^{-1/2} S D^{-1/2}.  Lanczos converges to *extremal*
eigenvalues, so to get the k smallest of L_sym (spectrum in [0, 2]) we run
it on the shifted operator A = 2I - L_sym = I + D^{-1/2} S D^{-1/2}, whose
largest eigenpairs are exactly L_sym's smallest: the iteration then needs
no shift-and-invert solve, only products with S.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.similarity import UpperSim, sym_matmat, sym_matvec
from repro.precision import matmul


def dense_degrees(S: jax.Array) -> jax.Array:
    return jnp.sum(S, axis=1)


def masked_inv_sqrt(deg: jax.Array) -> jax.Array:
    """D^{-1/2} with zero-degree rows (padding, isolated vertices) pinned to 0
    so they stay in the null space of the normalized-similarity term."""
    return jnp.where(deg > 0, 1.0 / jnp.sqrt(jnp.maximum(deg, 1e-12)), 0.0)


def _dense_matmat(S: jax.Array, inv_sqrt: jax.Array, valid: jax.Array,
                  V: jax.Array) -> jax.Array:
    return valid[:, None] * V + inv_sqrt[:, None] * (
        matmul(S, inv_sqrt[:, None] * V))


def make_dense_operator(S: jax.Array, valid: jax.Array):
    """Shifted normalized operator from a dense padded similarity matrix.

    ``A V = valid * V + D^{-1/2} S D^{-1/2} V`` — the single construction
    shared by the full/dense/precomputed affinity paths.  ``S`` is
    (n_pad, n_pad) with zero padding rows/cols; ``valid`` the (n_pad,)
    1/0 mask.  Returns ``(matmat, inv_sqrt)``: the canonical multi-vector
    product (one pass of S per (n_pad, b) block — with S row-sharded and
    the block replicated, ``S @ .`` is the one collective) plus D^{-1/2}
    for out-of-sample extension.

    ``matmat`` is a :class:`jax.tree_util.Partial` of a module-level
    function over ``(S, inv_sqrt, valid)``: data, not a closure, so the
    Lanczos recurrence takes it as an argument and every fit of the same
    shapes reuses one compiled loop (``core.lanczos.block_run``).
    """
    deg = matmul(S, valid)  # padded cols are zero already
    inv_sqrt = masked_inv_sqrt(deg)
    return jax.tree_util.Partial(_dense_matmat, S, inv_sqrt, valid), inv_sqrt


# A TPU v5e gathers rows of 8 lanes about three times faster than
# single values (PERF.md, section 6), so a narrower block is gathered 8
# wide.  XLA would shrink a zero-padded gather back to single values;
# the optimization barrier keeps it wide.
_GATHER_LANES = 8


def _sparse_matmat(cols: jax.Array, nw: jax.Array, valid: jax.Array,
                   V: jax.Array) -> jax.Array:
    b = V.shape[1]
    U = V
    if b < _GATHER_LANES:
        U = lax.optimization_barrier(
            jnp.pad(V, ((0, 0), (0, _GATHER_LANES - b))))
    NV = jnp.sum(nw[:, :, None] * U[cols], axis=1)[:, :b]
    return valid[:, None] * V + NV


def make_sparse_operator(cols: jax.Array, w: jax.Array, valid: jax.Array):
    """:func:`make_dense_operator` for a similarity given by its nonzeros
    row by row: ``S[i, cols[i, s]] = w[i, s]`` for the (n_pad, width)
    arrays, each position at most once (spare slots carry weight 0).

    The degrees are the rows' sums, and the normalization is folded into
    the weights once: a pass of ``matmat`` is a gather of ``V``'s rows, a
    multiply and a row sum over the nonzeros, and never reads an
    (n_pad, n_pad) matrix.  Returns ``(matmat, inv_sqrt)``, ``matmat`` a
    :class:`jax.tree_util.Partial` over ``(cols, D^-1/2 S D^-1/2
    weights, valid)``: one compiled Lanczos loop per shape, as for the
    dense operator."""
    inv_sqrt, nw = _sparse_scales(cols, w)
    return jax.tree_util.Partial(_sparse_matmat, cols, nw, valid), inv_sqrt


@jax.jit
def _sparse_scales(cols, w):
    # one program, not a dozen eager dispatches: on a TPU v5e those were
    # most of the graph's affinity time (PERF.md, section 6)
    inv_sqrt = masked_inv_sqrt(jnp.sum(w, axis=1))
    return inv_sqrt, w * (inv_sqrt[:, None] * inv_sqrt[cols])


def sparse_to_dense(cols: jax.Array, w: jax.Array) -> jax.Array:
    """The (n_pad, n_pad) matrix of :func:`make_sparse_operator`'s
    nonzeros, on the device."""
    n_pad = cols.shape[0]
    rows = jnp.broadcast_to(jnp.arange(n_pad)[:, None], cols.shape)
    return jnp.zeros((n_pad, n_pad), w.dtype).at[rows, cols].add(w)


def dense_shifted_matrix(S: jax.Array, valid: jax.Array,
                         inv_sqrt: jax.Array | None = None) -> jax.Array:
    """Materialized A = diag(valid) + D^{-1/2} S D^{-1/2} (for exact eigh).

    Pass the operator build's ``inv_sqrt`` when you have it — recomputing
    it here costs a redundant full pass over S."""
    if inv_sqrt is None:
        inv_sqrt = masked_inv_sqrt(matmul(S, valid))
    return jnp.diag(valid) + S * (inv_sqrt[:, None] * inv_sqrt[None, :])


def dense_lsym(S: jax.Array, deg: jax.Array | None = None) -> jax.Array:
    inv_sqrt = masked_inv_sqrt(dense_degrees(S) if deg is None else deg)
    N = S * inv_sqrt[:, None] * inv_sqrt[None, :]
    return jnp.eye(S.shape[0], dtype=S.dtype) - N


def degrees(upper: UpperSim) -> jax.Array:
    """d_i = sum_j S_ij via one symmetric mat-vec with the ones vector."""
    ones = upper.diag  # 1.0 on valid (permuted) rows, 0 on padding
    return sym_matvec(upper, ones)


def make_shifted_matmat(
    upper: UpperSim, deg: jax.Array
) -> Callable[[jax.Array], jax.Array]:
    """A V = V + D^{-1/2} S D^{-1/2} V on (n_pad, b) blocks, padding rows
    mapped to 0.

    Padding rows have degree 0; we pin their inv-sqrt to 0 so they stay in
    the null space of the S-term and contribute nothing.  The identity term
    is masked to valid rows so pad rows don't pollute the Krylov basis.
    The inner :func:`~repro.core.similarity.sym_matmat` streams each
    device's triangle tiles once per block.
    """
    valid = upper.diag  # (n_pad,) 1/0
    inv_sqrt = jnp.where(deg > 0, 1.0 / jnp.sqrt(jnp.maximum(deg, 1e-12)), 0.0)

    def matmat(V: jax.Array) -> jax.Array:
        SV = sym_matmat(upper, inv_sqrt[:, None] * V)
        return valid[:, None] * V + inv_sqrt[:, None] * SV

    return matmat


def make_dense_shifted_matmat(
    S: jax.Array, deg: jax.Array | None = None
) -> Callable[[jax.Array], jax.Array]:
    """``deg`` threads a degree vector the caller already computed through
    (one full pass over S saved per operator construction)."""
    inv_sqrt = masked_inv_sqrt(dense_degrees(S) if deg is None else deg)

    def matmat(V: jax.Array) -> jax.Array:
        return V + inv_sqrt[:, None] * matmul(S, inv_sqrt[:, None] * V)

    return matmat
