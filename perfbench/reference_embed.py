"""The plain reference of the embedding cell (``fit_points``), in float64
from the same bf16 rows the job reads.

It imports nothing of the program under test.  The affinity is the RBF
kernel with the median heuristic's bandwidth, the diagonal kept:
``S_ij = exp(-|x_i - x_j|^2 / 2 sigma^2)``, ``sigma^2`` the median of the
squared distances among the first 1,024 rows; ``N = D^-1/2 S D^-1/2``
with ``D = diag(S 1)``, and ``L_sym = I - N``.

The degrees of all rows (:func:`degrees`) are plain jnp on JAX's default
device, the rows' products exact in float32 and each row's sum finished
in float64 on the host.  One application of the whole operator costs
2 n^2 d = 3.5e13 flop at the cell's size, too slow for the host, so the
check also builds float64 rows of ``S`` on the host for a seeded sample
``R`` of rows, and takes R's degrees from them:

``deg_rel``    the widest relative gap, over all rows, between the job's
               degrees (its ``D^-1/2``) and the reference's;
``resid_k``    the widest, over the k pairs, relative residual
               ``|(N Z - Z diag(1 - lambda))_R| / |Z_R|``, the rows of
               ``N`` on R in float64 from ``S_R`` and the reference's
               degrees;
``pass_rel``   one pass of the program's operator at the job's block
               width, on seeded Gaussian columns: the relative gap of
               its ``N V`` on R from the float64 ``(N V)_R``;
``label_gap``  over all rows, the widest gap by which a row's float64
               squared distance to its labelled center exceeds that to
               the nearest of the job's centers, rows of Z normalized.

Readings, not compared: ``deg_ref_gap`` (the device degrees against the
float64 ones on R), ``orth`` (the widest entry of ``Z^T Z - I``) and
``ari`` (adjusted Rand index of the labels against the planted topics).
"""
from __future__ import annotations

import numpy as np

SIGMA_ROWS = 1024        # rows the median heuristic looks at
CHUNK = 8192             # columns of S built at a time
DEG_ROWS = 1024          # rows of S per device block in ``degrees``
DEG_PART = 512           # columns a device block sums before the host


def nan_first(v):
    return (v != v, v if v == v else 0.0)


def sample_rows(n: int, size: int, seed: int) -> np.ndarray:
    """A seeded sample of ``size`` distinct rows, sorted."""
    rng = np.random.default_rng([seed, 0x5eed])
    return np.sort(rng.choice(n, size=min(size, n), replace=False))


def _f64(x) -> np.ndarray:
    return np.asarray(x, np.float64)


def median_sigma(x) -> float:
    """sqrt of the median squared distance among the first rows."""
    xs = _f64(x[:SIGMA_ROWS])
    sq = np.sum(xs * xs, axis=1)
    d2 = np.maximum(sq[:, None] + sq[None, :] - 2.0 * xs @ xs.T, 0.0)
    return float(np.sqrt(np.median(d2[np.triu_indices(len(xs), k=1)])
                         + 1e-12))


def affinity_rows(x, rows: np.ndarray, sigma: float) -> np.ndarray:
    """Float64 rows ``S[rows, :]`` of the RBF affinity of ``x``."""
    xr = _f64(x[rows])
    sr = np.sum(xr * xr, axis=1)
    out = np.empty((len(rows), len(x)))
    for c0 in range(0, len(x), CHUNK):
        xc = _f64(x[c0:c0 + CHUNK])
        sc = np.sum(xc * xc, axis=1)
        d2 = np.maximum(sr[:, None] + sc[None, :] - 2.0 * xr @ xc.T, 0.0)
        out[:, c0:c0 + CHUNK] = np.exp(-d2 / (2.0 * sigma * sigma))
    return out


LN2_HI, LN2_LO = 0.693145751953125, 1.428606765330187e-06   # ln 2, split


def exp32(a):
    """``exp(a)`` in float32 within 1e-7 (relative) for the RBF's
    ``a <= 0``: ``a = k ln2 + r``, ``|r| <= ln2 / 2``, ``exp(r)`` by its
    Taylor polynomial of degree 10, times ``2^k``.  Written out so that
    the reference does not lean on the device's own exp (a TPU's reads
    about 1.7e-6 high on these arguments)."""
    import jax.numpy as jnp
    k = jnp.round(a * (1.0 / np.log(2.0)))
    r = (a - k * np.float32(LN2_HI)) - k * np.float32(LN2_LO)
    p = jnp.ones_like(r)
    for i in range(10, 0, -1):
        p = 1.0 + p * r / i
    return jnp.ldexp(p, k.astype(jnp.int32))


def degrees(x, sigma: float) -> np.ndarray:
    """Float64 ``S 1`` of every row.  On JAX's default device, per block
    of rows: the Gram against all rows in float32 at HIGHEST (bf16 values
    and their products are exact there), the exp in float32
    (:func:`exp32`), row sums over ``DEG_PART`` columns; the host adds
    those in float64."""
    import math

    import jax
    import jax.numpy as jnp

    n = len(x)
    part = math.gcd(n, DEG_PART)
    xd = jnp.asarray(np.asarray(x, np.float32))
    sq = jnp.sum(xd * xd, axis=1)
    inv2s2 = 1.0 / (2.0 * sigma * sigma)

    @jax.jit
    def block(xr, sr, xd, sq):
        g = jnp.matmul(xr, xd.T, precision=jax.lax.Precision.HIGHEST)
        s = exp32(-jnp.maximum(sr[:, None] + sq[None, :] - 2.0 * g, 0.0)
                  * inv2s2)
        return s.reshape(len(xr), n // part, part).sum(-1)

    out = np.empty(n)
    for r0 in range(0, n, DEG_ROWS):
        r1 = min(r0 + DEG_ROWS, n)
        out[r0:r1] = np.asarray(block(xd[r0:r1], sq[r0:r1], xd, sq),
                                np.float64).sum(1)
    return out


def pass_gap(S_R: np.ndarray, rows: np.ndarray, deg: np.ndarray, V,
             out) -> float:
    """The relative gap, on ``rows``, between the ``N V`` of one pass
    ``out = V + N V`` and float64's, over the whole block."""
    V, out = _f64(V), _f64(out)
    inv = deg ** -0.5
    want = inv[rows, None] * (S_R @ (inv[:, None] * V))
    got = out[rows] - V[rows]
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want), 1e-300))


def normalize_rows(Z) -> np.ndarray:
    Z = _f64(Z)
    return Z / np.maximum(np.linalg.norm(Z, axis=1, keepdims=True), 1e-12)


def assign_gap(Y, centers, labels) -> float:
    """The widest, over rows, float64 squared distance to the labelled
    center minus that to the nearest center."""
    C = _f64(centers)
    lab = np.asarray(labels, np.int64)
    worst = 0.0
    for r0 in range(0, len(Y), CHUNK):
        y = _f64(Y[r0:r0 + CHUNK])
        d2 = ((y[:, None, :] - C[None, :, :]) ** 2).sum(-1)
        gap = d2[np.arange(len(y)), lab[r0:r0 + CHUNK]] - d2.min(1)
        worst = max(worst, float(gap.max()))
    return worst


def ari(a, b) -> float:
    """Adjusted Rand index of two labelings."""
    a, b = np.asarray(a), np.asarray(b)
    _, ai = np.unique(a, return_inverse=True)
    _, bi = np.unique(b, return_inverse=True)
    table = np.zeros((ai.max() + 1, bi.max() + 1))
    np.add.at(table, (ai, bi), 1)

    def pairs(v):
        return float(np.sum(v * (v - 1) / 2))

    total = pairs(np.asarray([len(a)], np.float64))
    both = pairs(table)
    rows, cols = pairs(table.sum(1)), pairs(table.sum(0))
    expected = rows * cols / total
    top = (rows + cols) / 2
    return 1.0 if top == expected else (both - expected) / (top - expected)


def job_numbers(S_R: np.ndarray, rows: np.ndarray, deg: np.ndarray,
                out: dict, topics) -> dict:
    """One job's readings (module docstring) from its degrees
    (``inv_sqrt``), eigenpairs (``evals``, ``Z``), ``centers`` and
    ``labels``, against the reference's degrees ``deg`` of all rows and
    its rows ``S_R``."""
    inv = _f64(out["inv_sqrt"])
    Z = _f64(out["Z"])
    lam = _f64(out["evals"])
    if not (np.all(np.isfinite(Z)) and np.all(np.isfinite(lam))
            and np.all(np.isfinite(inv)) and np.all(inv > 0)):
        return dict.fromkeys(("deg_rel", "resid_k", "label_gap", "orth",
                              "ari"), float("nan"))
    deg_rel = float(np.max(np.abs(1.0 / inv ** 2 - deg) / deg))
    inv_ref = deg ** -0.5
    NZ_R = inv_ref[rows, None] * (S_R @ (inv_ref[:, None] * Z))
    R = NZ_R - Z[rows] * (1.0 - lam)[None, :]
    resid = np.linalg.norm(R, axis=0) / np.maximum(
        np.linalg.norm(Z[rows], axis=0), 1e-300)
    k = Z.shape[1]
    return {"deg_rel": deg_rel, "resid_k": float(resid.max()),
            "label_gap": assign_gap(normalize_rows(Z), out["centers"],
                                    out["labels"]),
            "orth": float(np.max(np.abs(Z.T @ Z - np.eye(k)))),
            "ari": ari(out["labels"], topics)}
