"""The plain float64 NumPy reference for spectral clustering.

Independent of the system under test (no JAX, no kernels, no registry):
dense RBF similarity with the diagonal kept, ``L_sym = I - D^{-1/2} S
D^{-1/2}``, ``np.linalg.eigh`` for the k smallest eigenpairs, unit rows,
then Lloyd's k-means from k-means++ seeds (best of ``restarts`` by
inertia).  The same semantics as ``SpectralClustering`` with an RBF
affinity, so its labels and eigenvalues are what a fit is checked
against.  Dense and O(n^3): meant for n up to a few thousand.

For a graph (the paper's topology input) :func:`graph_components` gives
the exact multiplicity of the zero eigenvalue of ``L_sym`` (one per
connected component), and :func:`graph_lanczos_reference` runs the same
single-vector Lanczos recurrence as the ``"lanczos"`` eigensolver, in
float64 on the sparse graph: what that many steps give without float32
rounding.
"""
from __future__ import annotations

import numpy as np


def spectral_reference(x: np.ndarray, k: int, sigma: float, *,
                       seed: int = 0, iters: int = 100,
                       restarts: int = 4) -> tuple[np.ndarray, np.ndarray]:
    """(labels (n,), the k smallest L_sym eigenvalues ascending)."""
    x = np.asarray(x, np.float64)
    sq = np.sum(x * x, axis=1)
    d2 = np.maximum(sq[:, None] + sq[None, :] - 2.0 * x @ x.T, 0.0)
    S = np.exp(-d2 / (2.0 * float(sigma) ** 2))
    inv_sqrt = 1.0 / np.sqrt(S.sum(axis=1))
    L = np.eye(len(x)) - S * inv_sqrt[:, None] * inv_sqrt[None, :]
    evals, evecs = np.linalg.eigh(L)                    # ascending
    Y = evecs[:, :k]
    Y = Y / np.maximum(np.linalg.norm(Y, axis=1, keepdims=True), 1e-12)
    rng = np.random.RandomState(seed)
    best, best_inertia = None, np.inf
    for _ in range(restarts):
        labels, inertia = _lloyd(Y, k, rng, iters)
        if inertia < best_inertia:
            best, best_inertia = labels, inertia
    return best, evals[:k]


def _lloyd(Y: np.ndarray, k: int, rng: np.random.RandomState,
           iters: int) -> tuple[np.ndarray, float]:
    centers = [Y[rng.randint(len(Y))]]                  # k-means++ seeds
    for _ in range(1, k):
        d2 = np.min([np.sum((Y - c) ** 2, axis=1) for c in centers], axis=0)
        centers.append(Y[rng.choice(len(Y), p=d2 / d2.sum())])
    C = np.array(centers)
    for _ in range(iters):
        d2 = np.sum((Y[:, None, :] - C[None, :, :]) ** 2, axis=2)
        labels = np.argmin(d2, axis=1)
        C_new = np.array([Y[labels == j].mean(axis=0) if np.any(labels == j)
                          else C[j] for j in range(k)])
        if np.allclose(C_new, C):
            break
        C = C_new
    d2 = np.sum((Y[:, None, :] - C[None, :, :]) ** 2, axis=2)
    return np.argmin(d2, axis=1), float(np.min(d2, axis=1).sum())


def _graph_csr(n: int, edges: np.ndarray):
    """Rows, columns and weights of the symmetric adjacency with a unit
    diagonal, as ``graph_file.adjacency_dense`` builds it."""
    i, j, w = (np.asarray(edges[:, c]) for c in range(3))
    rows = np.concatenate([i, j, np.arange(n)])
    cols = np.concatenate([j, i, np.arange(n)])
    return rows, cols, np.concatenate([w, w, np.ones(n)]).astype(np.float64)


def graph_components(n: int, edges: np.ndarray) -> int:
    """Connected components of the graph: the multiplicity of ``L_sym``'s
    zero eigenvalue."""
    parent = np.arange(n)

    def root(a):
        while parent[a] != a:
            parent[a] = parent[parent[a]]
            a = parent[a]
        return a

    for a, b in np.asarray(edges[:, :2]):
        ra, rb = root(a), root(b)
        if ra != rb:
            parent[ra] = rb
    return len({root(a) for a in range(n)})


def graph_lanczos_reference(n: int, edges: np.ndarray, v0: np.ndarray,
                            steps: int, k: int) -> np.ndarray:
    """The k smallest ``L_sym`` Ritz values (ascending) of ``steps``
    single-vector Lanczos steps with full (CGS2) reorthogonalization on
    ``A = I + D^{-1/2} S D^{-1/2}``, from start vector ``v0``, in
    float64.  ``v0`` may be longer than n: the padding rows are zero rows
    of A, as in the estimator's operator."""
    rows, cols, w = _graph_csr(n, edges)
    inv_sqrt = 1.0 / np.sqrt(np.bincount(rows, weights=w, minlength=n))
    wn = w * inv_sqrt[rows] * inv_sqrt[cols]
    n_pad = len(v0)
    valid = (np.arange(n_pad) < n).astype(np.float64)

    def apply(v):
        out = valid * v
        out[:n] += np.bincount(rows, weights=wn * v[cols], minlength=n)
        return out

    V = np.zeros((steps + 1, n_pad))
    V[0] = v0 / np.linalg.norm(v0)
    alpha, beta = np.zeros(steps), np.zeros(steps + 1)
    for j in range(steps):
        u = apply(V[j]) - (beta[j] * V[j - 1] if j else 0.0)
        alpha[j] = V[j] @ u
        u -= alpha[j] * V[j]
        for _ in range(2):
            u -= V[: j + 1].T @ (V[: j + 1] @ u)
        beta[j + 1] = np.linalg.norm(u)
        V[j + 1] = u / beta[j + 1] if beta[j + 1] > 1e-8 else 0.0
    T = (np.diag(alpha) + np.diag(beta[1:steps], 1)
         + np.diag(beta[1:steps], -1))
    return np.sort(2.0 - np.linalg.eigvalsh(T))[:k]
