"""Pure-jnp oracles for every Pallas kernel in this package."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def rbf_similarity(x: jax.Array, y: jax.Array, sigma) -> jax.Array:
    """S_ij = exp(-||x_i - y_j||^2 / (2 sigma^2))."""
    xx = jnp.sum(x * x, axis=-1)[:, None]
    yy = jnp.sum(y * y, axis=-1)[None, :]
    d2 = jnp.maximum(xx + yy - 2.0 * (x @ y.T), 0.0)
    return jnp.exp(-d2 / (2.0 * jnp.asarray(sigma, x.dtype) ** 2))


def fused_rbf_matmat(x: jax.Array, y: jax.Array, V: jax.Array, sigma,
                     row_scale: jax.Array, col_scale: jax.Array) -> jax.Array:
    """diag(row_scale) @ RBF(x, y) @ diag(col_scale) @ V — materialized,
    in float32 from the rows' own values (bf16 rows are widened exactly)."""
    f32 = jnp.float32
    S = rbf_similarity(x.astype(f32), y.astype(f32), sigma)
    return row_scale[:, None] * (S @ (col_scale[:, None] * V))


def fused_nystrom_matmat(x: jax.Array, y: jax.Array, V: jax.Array, sigma,
                         col_scale: jax.Array,
                         col_valid: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(RBF(x, y) @ (col_scale * V), RBF(x, y) @ col_valid) — materialized."""
    K = rbf_similarity(x, y, sigma)
    return K @ (col_scale[:, None] * V), (K @ col_valid)[:, None]


def block_matvec(A: jax.Array, v: jax.Array) -> jax.Array:
    """A @ v."""
    return A @ v


def block_matmat(A: jax.Array, V: jax.Array) -> jax.Array:
    """A @ V."""
    return A @ V


def flash_attention(q, k, v, scale=None, causal=True, window=-1):
    """Oracle softmax attention. q/k/v: (B, H, S|T, hd)."""
    hd = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (hd ** 0.5)
    s = jnp.einsum("bhqd,bhtd->bhqt", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    S, T = q.shape[2], k.shape[2]
    qpos = jnp.arange(S)[:, None]
    kpos = jnp.arange(T)[None, :]
    ok = jnp.ones((S, T), bool)
    if causal:
        ok = ok & (kpos <= qpos)
    if window > 0:
        ok = ok & (qpos - kpos < window)
    s = jnp.where(ok, s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqt,bhtd->bhqd", w, v.astype(jnp.float32)).astype(q.dtype)


def kmeans_assign(points: jax.Array, centers: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(argmin_j ||p_i - c_j||^2, min_j ||p_i - c_j||^2)."""
    pp = jnp.sum(points * points, axis=-1)[:, None]
    cc = jnp.sum(centers * centers, axis=-1)[None, :]
    d2 = jnp.maximum(pp + cc - 2.0 * (points @ centers.T), 0.0)
    return jnp.argmin(d2, axis=1).astype(jnp.int32), jnp.min(d2, axis=1)
