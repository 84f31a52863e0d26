"""The plain reference the benchmark's comparisons are made against.

It imports nothing of the program under test and takes none of its
scales or tables: the operator and every product are computed here
again, in float64 on the host, from the cell's own edge list.

* Graphs: the adjacency of the edge list with unit self-loops, its
  normalized operator ``N = D^-1/2 A D^-1/2`` and ``L_sym = I - N``.
* Eigenpairs: what a Lanczos run of a given number of steps yields are
  Ritz pairs of one Krylov space.  Under the reference operator they
  satisfy the Rayleigh-Ritz conditions (``Z^T N Z`` is diagonal with the
  reported ``1 - lambda``), their residuals ``N Z - Z (1 - Lambda)`` are
  all multiples of one vector (the next Lanczos vector), and a pair that
  has converged has a residual at rounding level.
* Assignments: each row of the row-normalized embedding goes to the
  nearest center by squared distance in float64.
"""
from __future__ import annotations

import numpy as np

def graph_operator(n: int, edges: np.ndarray):
    """Degrees of the graph (unit self-loops) and ``v -> N v`` (float64)."""
    i, j = edges[:, 0].astype(np.int64), edges[:, 1].astype(np.int64)
    w = edges[:, 2].astype(np.float64)
    deg = np.ones(n) + np.bincount(i, w, n) + np.bincount(j, w, n)
    inv = 1.0 / np.sqrt(deg)

    def apply_n(V):
        U = inv[:, None] * np.asarray(V, np.float64)
        out = U.copy()                          # the self-loops
        np.add.at(out, i, w[:, None] * U[j])
        np.add.at(out, j, w[:, None] * U[i])
        return inv[:, None] * out

    return deg, apply_n


def eigen_numbers(evals, Z, apply_n) -> dict:
    """How far the reported pairs of L_sym are from Ritz pairs of the
    reference operator (columns of ``Z`` scaled to unit norm):

    ``ritz_gap``    widest entry of ``Z^T N Z - diag(1 - lambda)``: the
                    Rayleigh-Ritz conditions, eigenvalues and coupling;
    ``resid_first`` relative residual of the smallest pair, the one the
                    Krylov space resolves first;
    ``resid_rank2`` second singular value of the residual matrix, which
                    a Lanczos run leaves of rank one;
    ``eig_resid``   widest relative residual over all pairs (a reading:
                    pairs a short run has not converged read large).
    """
    Z = np.asarray(Z, np.float64)
    lam = np.asarray(evals, np.float64)
    norms = np.linalg.norm(Z, axis=0)
    if not (np.all(np.isfinite(Z)) and np.all(np.isfinite(lam))
            and np.all(norms > 0)):
        return dict.fromkeys(("ritz_gap", "resid_first", "resid_rank2",
                              "eig_resid"), float("nan"))
    Z = Z / norms
    NZ = apply_n(Z)
    H = Z.T @ NZ
    R = NZ - (1.0 - lam)[None, :] * Z
    cols = np.linalg.norm(R, axis=0)
    sv = np.linalg.svd(R, compute_uv=False)
    first = int(np.argmin(lam))
    return {"ritz_gap": float(np.max(np.abs(H - np.diag(1.0 - lam)))),
            "resid_first": float(cols[first]),
            "resid_rank2": float(sv[1]) if len(sv) > 1 else 0.0,
            "eig_resid": float(cols.max())}


def normalize_rows(Z) -> np.ndarray:
    Z = np.asarray(Z, np.float64)
    return Z / np.maximum(np.linalg.norm(Z, axis=1, keepdims=True), 1e-12)


def assign_gaps(Y, centers, labels) -> np.ndarray:
    """Per row: squared distance to the labelled center minus that to the
    nearest center, in float64 (0 where the label is the nearest)."""
    Y = np.asarray(Y, np.float64)
    C = np.asarray(centers, np.float64)
    d2 = ((Y[:, None, :] - C[None, :, :]) ** 2).sum(-1)
    lab = np.asarray(labels, np.int64)
    return d2[np.arange(len(Y)), lab] - d2.min(1)
