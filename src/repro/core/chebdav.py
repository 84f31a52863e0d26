"""Block Chebyshev–Davidson: phase-2 alternative to (block) Lanczos.

The distributed block Chebyshev–Davidson method for spectral clustering
(Pang & Yang 2022) computes the k *largest* eigenpairs of the shifted
normalized operator A = 2I - L_sym (spectrum in [0, 2]) by repeatedly

  1. taking the current best block of b Ritz vectors,
  2. pushing it through a degree-d Chebyshev polynomial filter that damps
     the unwanted (lower) part of the spectrum and amplifies the wanted
     (upper) end — d matrix passes that need NO inner products or
     orthogonalization, the cheap streaming part,
  3. orthogonalizing the filtered block against the search basis (CGS2 +
     QR) and appending it,
  4. Rayleigh–Ritz on the grown basis, restarting when it exceeds
     ``max_subspace``.

Every matrix pass is a width-b ``matmat``, so like block Lanczos each
sweep of the similarity matrix is amortized over the whole block; unlike
Lanczos the filter concentrates the spectrum first, so far fewer passes
reach the same residual on clustered spectra.

Everything here is a host-side driver over jitted jnp kernels: the n×b
block algebra is XLA, the convergence control flow is Python (the same
split as the engine's streaming consumers).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.precision import matmul


@dataclass
class ChebDavResult:
    """Top-k eigenpairs of the operator the filter ran on (A, descending
    wanted end), plus convergence counters."""
    evals: jax.Array      # (k,) eigenvalues of A, descending
    evecs: jax.Array      # (n, k) unit columns
    iters: int            # outer Davidson iterations
    passes: int           # matrix passes (matmat applications, any width)
    max_residual: float   # max ||A x - theta x|| over the k wanted pairs


def chebyshev_filter(matmat: Callable, X: jax.Array, degree: int,
                     a: float, b: float, a0: float) -> jax.Array:
    """Scaled Chebyshev filter: damps the operator spectrum inside
    ``[a, b]``, amplifies outside, normalized at ``a0`` (the wanted end)
    so iterates neither overflow nor vanish (Zhou & Saad's three-term
    recurrence, mirrored to the upper end of the spectrum).

    ``degree`` matrix passes of width X.shape[1]; no orthogonalization.
    """
    e = max(0.5 * (b - a), 1e-6)
    c = 0.5 * (b + a)
    sigma = e / (c - a0)
    tau = 2.0 / sigma
    Y = (matmat(X) - c * X) * (sigma / e)
    for _ in range(2, degree + 1):
        sigma_new = 1.0 / (tau - sigma)
        Yt = (matmat(Y) - c * Y) * (2.0 * sigma_new / e) \
            - (sigma * sigma_new) * X
        X, Y = Y, Yt
        sigma = sigma_new
    return Y


def _orthonormalize_against(basis: jax.Array, W: jax.Array,
                            eps: float = 1e-8) -> jax.Array:
    """CGS2 against ``basis`` then QR within ``W``; (near-)dependent
    columns are dropped, so the returned block may be narrower than W."""
    for _ in range(2):
        W = W - matmul(basis, matmul(basis.T, W))
    Q, R = jnp.linalg.qr(W)
    keep = np.asarray(jnp.abs(jnp.diagonal(R))) > eps
    if not keep.any():
        return Q[:, :0]
    return Q[:, np.flatnonzero(keep)]


def chebdav(matmat: Callable, n: int, k: int, key: jax.Array, *,
            block_size: Optional[int] = None, degree: int = 12,
            tol: float = 1e-5, max_iters: int = 100,
            max_subspace: Optional[int] = None,
            valid: Optional[jax.Array] = None,
            dtype=jnp.float32) -> ChebDavResult:
    """k largest eigenpairs of the symmetric operator behind ``matmat``
    (spectrum assumed within [0, 2] — the shifted normalized operator).

    ``valid`` optionally zeroes padding rows of the random start block so
    they never enter the search space (the operator annihilates them, so
    the invariant then holds for every later block).
    """
    b = int(block_size or max(2, min(k, n)))
    b = max(1, min(b, n))
    m_max = int(max_subspace or min(n, max(3 * b + k, 2 * k + b)))

    passes = 0

    def apply(X):
        nonlocal passes
        passes += 1
        return matmat(X)

    X0 = jax.random.normal(key, (n, b), dtype)
    if valid is not None:
        X0 = X0 * valid[:, None].astype(dtype)
    V = _orthonormalize_against(jnp.zeros((n, 0), dtype), X0)
    AV = apply(V)

    up = 2.0          # spectrum ceiling of A = I + D^-1/2 S D^-1/2
    lo = 0.0          # spectrum floor (padding rows / L_sym upper end)
    it = 0
    theta = jnp.zeros((k,), dtype)
    Z = V[:, :k]
    max_res = float("inf")
    best_res, stale = float("inf"), 0
    for it in range(1, max_iters + 1):
        H = matmul(V.T, AV)
        H = 0.5 * (H + H.T)
        evals, U = jnp.linalg.eigh(H)            # ascending
        m = int(H.shape[0])
        kw = min(k, m)                           # wanted pairs available
        Uw = U[:, m - kw:][:, ::-1]              # wanted, descending
        theta = evals[m - kw:][::-1]
        Rw = matmul(V, Uw)                       # wanted Ritz vectors
        ARw = matmul(AV, Uw)
        res = jnp.linalg.norm(ARw - Rw * theta[None, :], axis=0)
        res_np = np.asarray(res)
        max_res = float(res_np.max()) if kw else float("inf")
        Z = Rw
        if kw == k and max_res < tol:
            break
        # Stagnation guard: float32 operators (e.g. the engine's callback
        # stream) bottom out above very tight tolerances — stop burning
        # matrix passes once the residual has stopped improving.
        if kw == k:
            if max_res < 0.7 * best_res:
                best_res, stale = max_res, 0
            else:
                stale += 1
                if stale >= 8:
                    break

        # Filter bounds: damp [lo, cut] — everything below the wanted
        # set.  cut = largest unwanted Ritz value when one exists, else
        # mid-gap between the floor and the smallest wanted value.
        evn = np.asarray(evals)
        lo = float(min(lo, evn.min()))
        if m > kw:
            cut = float(evn[m - kw - 1])
        else:
            cut = 0.5 * (lo + float(evn[0]))
        cut = min(max(cut, lo + 1e-3), up - 1e-3)
        a0 = max(float(np.asarray(theta).max()), cut + 1e-2)

        # Next block: the b best not-yet-converged wanted directions,
        # topped up with the next-best Ritz vectors when most converged.
        order = [i for i in range(kw) if res_np[i] >= tol] \
            + [i for i in range(kw) if res_np[i] < tol]
        cols = jnp.asarray(order[:b], jnp.int32)
        X = Rw[:, cols]

        Y = chebyshev_filter(apply, X, int(degree), lo, cut, a0)
        Y = _orthonormalize_against(V, Y)
        if Y.shape[1] == 0:
            break                                # subspace exhausted
        if m + Y.shape[1] > m_max:               # thick restart first:
            keep = max(kw, min(m_max - int(Y.shape[1]), m))
            Uk = U[:, m - keep:]                 # top Ritz directions of
            V = matmul(V, Uk)                    # the current basis (Y is
            AV = matmul(AV, Uk)                  # orthogonal to any subspan)
        V = jnp.concatenate([V, Y], axis=1)
        AV = jnp.concatenate([AV, apply(Y)], axis=1)

    norms = jnp.linalg.norm(Z, axis=0, keepdims=True)
    Z = Z / jnp.maximum(norms, 1e-12)
    return ChebDavResult(evals=theta, evecs=Z, iters=it, passes=passes,
                         max_residual=max_res)
