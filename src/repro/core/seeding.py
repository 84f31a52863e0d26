"""k-means++ seeding (D^2 sampling) — the ONE implementation every
phase-3 consumer shares.

The paper leaves the k-means init unspecified; plain random init
frequently collapses on spectral embeddings, so every assigner here seeds
with D^2 sampling.  Two substrate twins of the same algorithm live in
this module so it is written (and fixed) exactly once per substrate:

  * :func:`kmeans_plusplus_init` — jax, jitted (``lax.fori_loop``),
    used by ``core.kmeans`` (reference/distributed/mini-batch Lloyd) and
    by the registry assigners in ``cluster.assigners``;
  * :func:`kmeans_plusplus_np` — host numpy over a seeded
    ``RandomState``, used by the engine's streaming k-means, whose whole
    point is never materializing the embedding on device.

Both draw the first center weight-proportionally, then k-1 centers
proportionally to the weighted squared distance to the nearest chosen
center; ``weights`` masks padding rows out of the draw.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


@partial(jax.jit, static_argnames=("k",))
def kmeans_plusplus_init(y: jax.Array, k: int, key: jax.Array,
                         weights: jax.Array | None = None) -> jax.Array:
    """k-means++ seeding (D^2 sampling), jax substrate.  Jitted with ``k``
    static: one program per shape, reused by every later fit."""
    n = y.shape[0]
    w = weights if weights is not None else jnp.ones((n,), y.dtype)
    key, sub = jax.random.split(key)
    first = jax.random.choice(sub, n, p=w / jnp.sum(w))
    centers = jnp.zeros((k, y.shape[1]), y.dtype).at[0].set(y[first])
    d2 = jnp.sum((y - y[first]) ** 2, axis=1) * w

    def body(i, carry):
        centers, d2, key = carry
        key, sub = jax.random.split(key)
        p = d2 / jnp.maximum(jnp.sum(d2), 1e-12)
        idx = jax.random.choice(sub, n, p=p)
        c = y[idx]
        centers = centers.at[i].set(c)
        d2 = jnp.minimum(d2, jnp.sum((y - c) ** 2, axis=1) * w)
        return centers, d2, key

    centers, _, _ = lax.fori_loop(1, k, body, (centers, d2, key))
    return centers


def kmeans_plusplus_np(y: np.ndarray, k: int, rng: np.random.RandomState,
                       w: Optional[np.ndarray] = None) -> np.ndarray:
    """k-means++ seeding, host-numpy substrate (for samples that fit in
    RAM — the engine's reservoir sample)."""
    n = len(y)
    w = np.ones(n) if w is None else np.asarray(w, np.float64)
    centers = np.empty((k, y.shape[1]), np.float64)
    centers[0] = y[rng.choice(n, p=w / w.sum())]
    d2 = np.sum((y - centers[0]) ** 2, axis=1) * w
    for i in range(1, k):
        s = d2.sum()
        # all remaining distances zero (coincident points / k > #distinct):
        # fall back to weight-uniform draws instead of an invalid p vector
        p = d2 / s if s > 0 else w / w.sum()
        centers[i] = y[rng.choice(n, p=p)]
        d2 = np.minimum(d2, np.sum((y - centers[i]) ** 2, axis=1) * w)
    return centers
