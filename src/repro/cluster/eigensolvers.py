"""Eigensolver backends: phase 2 as pluggable strategies.

Signature:

    backend(est, op, key) -> (eigenvalues, Z, info)

``op`` is a :class:`~repro.cluster.operator.NormalizedOperator`;
``eigenvalues`` are the k smallest of L_sym (ascending) and ``Z`` the
matching (n_pad, k) eigenvector columns (unit norm), still in the
operator's (possibly permuted) row order.  Every backend reports
``info["matrix_passes"]`` — full sweeps over the similarity matrix
(one ``matmat`` of any width = one pass), the distributed cost unit of
the paper's §4.3 hot spot.  The Lanczos backends split their time into
the spans ``fit.eigensolve.krylov`` (the recurrence) and
``fit.eigensolve.ritz`` (the tridiagonal eigenproblem, solved on the
host in float64, and top-k), each ending when its device work has.

Backends:
  block-lanczos  shifted block Lanczos with full reorthogonalization
                 through ``op.matmat``: the same Krylov dimension in ~1/b
                 the matrix passes (each pass amortized over the b-wide
                 block).
  lanczos        the paper's Alg. 4.3: ``block-lanczos`` at b = 1, one
                 matrix pass per step.
  chebdav        block Chebyshev–Davidson (Pang & Yang 2022): degree-d
                 Chebyshev filtering of the current Ritz block between
                 Rayleigh–Ritz steps; ``est.block_size`` and
                 ``est.cheb_degree`` control the block width and filter
                 degree.
  eigh           exact dense eigendecomposition of the materialized
                 operator — the oracle, O(n^3), for tests / small n.

The two Lanczos names take a start block from the affinity
(``op.start_block``; their ``start_width`` attribute asks for one): the
first step's product then came with the degree pass, and
``matrix_passes`` counts the other steps.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import obs
from repro.cluster.registry import Registry
from repro.core import chebdav as cd, lanczos as lz

EIGENSOLVERS = Registry("eigensolver")

_SHIFT = 2.0  # A = shift*I - L_sym; see core.laplacian docstring


@EIGENSOLVERS.register("lanczos")
@EIGENSOLVERS.register("block-lanczos")
def block_lanczos_solver(est, op, key):
    b = est.num_block_size(op.n)       # same n as the step count below,
    steps = est.num_block_steps(op.n)  # so width and steps stay consistent
    start = op.start_block
    if start is not None and start[0].shape[1] != b:
        start = None
    with obs.span("fit.eigensolve.krylov"):
        state = jax.block_until_ready(lz.block_lanczos(
            op.matmat, op.n_pad, steps, key, block_size=b, dtype=est.dtype,
            host_matmat=op.host_matmat, start=start))
    # a folded first step rode on the affinity's degree pass
    passes = steps if start is None else steps - 1
    op.record_passes(passes, b)
    if start is not None:
        obs.counter("lanczos.start_folded").inc()
    with obs.span("fit.eigensolve.ritz"):
        evals, Z = jax.block_until_ready(
            lz.block_topk_of_shifted(state, est.k, shift=_SHIFT))
    return evals, Z, {"block_size": b, "block_steps": steps,
                      "matrix_passes": passes}


# asks an affinity that can for its first block's product with the
# degrees (SpectralClustering._start_request)
block_lanczos_solver.start_width = lambda est, n: est.num_block_size(n)


@EIGENSOLVERS.register("chebdav")
def chebdav_solver(est, op, key):
    b = est.num_block_size(op.n)
    res = cd.chebdav(op.matmat, op.n_pad, est.k, key, block_size=b,
                     degree=est.cheb_degree, valid=op.valid,
                     dtype=est.dtype)
    # res.evals are the largest of A, descending <-> smallest of L ascending
    vals = _SHIFT - res.evals
    return vals, res.evecs, {
        "block_size": b, "cheb_degree": est.cheb_degree,
        "chebdav_iters": res.iters, "matrix_passes": res.passes,
        "max_residual": res.max_residual}


@EIGENSOLVERS.register("eigh")
def eigh_solver(est, op, key):
    A = op.materialize()
    evals_A, evecs = jnp.linalg.eigh(A)  # ascending
    k = est.k
    # Largest of A are the smallest of L_sym; padding rows sit at A's
    # spectrum floor (eigenvalue 0) and never reach the top-k.
    Z = evecs[:, -k:][:, ::-1]
    vals = (_SHIFT - evals_A[-k:])[::-1]
    # Pass accounting for cross-solver comparability (the benchmark
    # sweep): the O(n^3) dense factorization sweeps the n_pad-row matrix
    # ~n_pad times — the iterative solvers' cost unit applied to eigh.
    return vals, Z, {"solver": "eigh", "matrix_passes": int(op.n_pad)}
