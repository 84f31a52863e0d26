"""Public jit'd wrappers for the Pallas kernels.

Handles: padding to tile multiples, backend dispatch (TPU -> compiled
kernel; CPU/other -> interpret mode, which runs the same kernel body in
Python for correctness), un-padding of results, and **schedule
resolution**: every wrapper takes ``schedule=`` — ``None`` reproduces the
keyword-tile defaults bit-for-bit, ``"auto"`` consults the persistent
schedule cache (:mod:`repro.tune.cache`), and a
:class:`~repro.tune.Schedule` (or dict of its fields) forces an explicit,
legality-checked schedule.  The per-kernel pad + interpret-autodetect +
legality boilerplate lives in one place (:func:`_resolve` /
:func:`_pad_rows`), not copy-pasted per wrapper.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import (block_matvec as _mv, kmeans_assign as _ka,
                           rbf_similarity as _rbf, ref)


_interpret_default = _mv.interpret_default   # one TPU-detection rule


def _resolve(kernel: str, schedule, *, bm=None, bn=None, compute_dtype=None,
             interpret=None, **shape):
    """One boilerplate site for every wrapper: resolve the schedule value
    against the call-site keyword defaults (auto-detecting ``interpret``
    when unset) and legality-check it for this kernel/shape.  Returns the
    concrete :class:`~repro.tune.Schedule`."""
    from repro.tune.schedule import resolve
    sched, _source = resolve(kernel, schedule, bm=bm, bn=bn,
                             compute_dtype=compute_dtype,
                             interpret=interpret, **shape)
    return sched


def _pad_rows(a: jax.Array, mult: int) -> tuple[jax.Array, int]:
    n = a.shape[0]
    n_pad = ((n + mult - 1) // mult) * mult
    if n_pad == n:
        return a, n
    pad = [(0, n_pad - n)] + [(0, 0)] * (a.ndim - 1)
    return jnp.pad(a, pad), n


def rbf_similarity(x: jax.Array, y: jax.Array, sigma, *, bm: int = 128,
                   bn: int = 128, interpret: bool | None = None,
                   schedule=None) -> jax.Array:
    """exp(-||x_i - y_j||^2 / 2 sigma^2) for all pairs; any (n, m)."""
    s = _resolve("rbf_similarity", schedule, bm=bm, bn=bn,
                 interpret=interpret, n=x.shape[0], m=y.shape[0],
                 d=x.shape[1])
    xp, n = _pad_rows(x, s.bm)
    yp, m = _pad_rows(y, s.bn)
    out = _rbf.rbf_similarity(xp, yp, sigma, bm=s.bm, bn=s.bn,
                              grid_order=s.grid_order, interpret=s.interpret)
    return out[:n, :m]


def fused_rbf_matmat(x: jax.Array, y: jax.Array, V: jax.Array, sigma,
                     row_scale: jax.Array | None = None,
                     col_scale: jax.Array | None = None, *,
                     bm: int = 128, bn: int = 128, compute_dtype=None,
                     interpret: bool | None = None, schedule=None
                     ) -> jax.Array:
    """diag(row_scale) @ RBF(x, y; sigma) @ diag(col_scale) @ V for any
    (n, d)/(m, d)/(m, b) — the similarity tile is recomputed in-register,
    never materialized.  Rows may be float32 or bfloat16 (kept as they
    are; see ``kernels.fused_rbf_matmat``).  Omitted scales default to
    ones; padded rows get scale 0 so they contribute nothing, and padded
    feature columns are zero."""
    from repro.kernels import fused_rbf_matmat as _frm
    n, m, d = x.shape[0], y.shape[0], x.shape[1]
    rows = _frm.row_dtype(x)
    s = _resolve("fused_rbf_matmat", schedule, bm=bm, bn=bn,
                 compute_dtype=compute_dtype, interpret=interpret,
                 n=n, m=m, d=d, b=V.shape[1], itemsize=rows.itemsize)
    rs = jnp.ones((n,), jnp.float32) if row_scale is None \
        else jnp.asarray(row_scale, jnp.float32)
    cs = jnp.ones((m,), jnp.float32) if col_scale is None \
        else jnp.asarray(col_scale, jnp.float32)
    d_pad = _frm.padded_width(d, s.bd)
    xp, _ = _pad_rows(x, s.bm)
    yp, _ = _pad_rows(y, s.bn)
    if d_pad != d:
        xp = jnp.pad(xp, ((0, 0), (0, d_pad - d)))
        yp = jnp.pad(yp, ((0, 0), (0, d_pad - d)))
    Vp, _ = _pad_rows(V, s.bn)
    rsp, _ = _pad_rows(rs, s.bm)
    csp, _ = _pad_rows(cs, s.bn)
    out = _frm.fused_rbf_matmat(xp, yp, Vp, sigma, rsp, csp, bm=s.bm,
                                bn=s.bn, bd=s.bd,
                                compute_dtype=s.compute_dtype,
                                acc=s.acc, interpret=s.interpret)
    return out[:n]


def fused_nystrom_matmat(x: jax.Array, y: jax.Array, V: jax.Array, sigma,
                         col_scale: jax.Array, col_valid: jax.Array | None = None,
                         *, bm: int = 128, bn: int = 128, compute_dtype=None,
                         interpret: bool | None = None, schedule=None
                         ) -> tuple[jax.Array, jax.Array]:
    """(K @ (col_scale * V), K @ col_valid) for K = RBF(x, y; sigma), any
    (m, d)/(n, d)/(n, b) — the serving-side fused pass: embedding product
    and query degree column from one in-register sweep over the training
    tiles.  ``col_valid`` defaults to ones on the true rows; padded
    training rows get scale/valid 0 so they contribute to neither output."""
    from repro.kernels import fused_rbf_matmat as _frm
    m, n = x.shape[0], y.shape[0]
    s = _resolve("fused_nystrom_matmat", schedule, bm=bm, bn=bn,
                 compute_dtype=compute_dtype, interpret=interpret,
                 m=m, n=n, d=x.shape[1], b=V.shape[1])
    cs = jnp.asarray(col_scale, jnp.float32)
    cv = jnp.ones((n,), jnp.float32) if col_valid is None \
        else jnp.asarray(col_valid, jnp.float32)
    xp, _ = _pad_rows(x, s.bm)
    yp, _ = _pad_rows(y, s.bn)
    Vp, _ = _pad_rows(V, s.bn)
    csp, _ = _pad_rows(cs, s.bn)
    cvp, _ = _pad_rows(cv, s.bn)
    O, deg = _frm.fused_nystrom_matmat(xp, yp, Vp, sigma, csp, cvp,
                                       bm=s.bm, bn=s.bn,
                                       compute_dtype=s.compute_dtype,
                                       acc=s.acc, interpret=s.interpret)
    return O[:m], deg[:m, 0]


def block_matmat(A: jax.Array, V: jax.Array, *, bm: int = 256, bn: int = 512,
                 interpret: bool | None = None, schedule=None) -> jax.Array:
    """A @ V for any (n, m) A and (m, b) V (one matrix pass per block)."""
    n, m = A.shape
    s = _resolve("block_matmat", schedule, bm=bm, bn=bn,
                 interpret=interpret, n=n, m=m, b=V.shape[1])
    Ap, _ = _pad_rows(A, s.bm)
    if m % s.bn:
        m_pad = ((m + s.bn - 1) // s.bn) * s.bn
        Ap = jnp.pad(Ap, ((0, 0), (0, m_pad - m)))
        Vp = jnp.pad(V, ((0, m_pad - m), (0, 0)))
    else:
        Vp = V
    out = _mv.block_matmat(Ap, Vp, bm=s.bm, bn=s.bn, acc=s.acc,
                           interpret=s.interpret)
    return out[:n]


def block_matvec(A: jax.Array, v: jax.Array, *, bm: int = 256, bn: int = 512,
                 interpret: bool | None = None, schedule=None) -> jax.Array:
    """A @ v for any (n, m) A — the width-1 view of :func:`block_matmat`."""
    return block_matmat(A, v.reshape(-1, 1), bm=bm, bn=bn,
                        interpret=interpret,
                        schedule=schedule).reshape(A.shape[0])


def kmeans_assign(points: jax.Array, centers: jax.Array, *, bm: int = 512,
                  interpret: bool | None = None, schedule=None
                  ) -> tuple[jax.Array, jax.Array]:
    """(labels, sq-dists) for any n; padded rows are discarded."""
    s = _resolve("kmeans_assign", schedule, bm=bm, interpret=interpret,
                 n=points.shape[0], d=points.shape[1], k=centers.shape[0])
    p, n = _pad_rows(points, s.bm)
    idx, dist = _ka.kmeans_assign(p, centers, bm=s.bm, interpret=s.interpret)
    return idx[:n], dist[:n]


def flash_attention(q, k, v, *, causal: bool = True, window: int = -1,
                    bq: int = 256, bk: int = 256,
                    interpret: bool | None = None):
    """Fused attention; q (B,H,S,hd), k/v (B,KV,T,hd) — kv heads are
    broadcast to H, sequences padded to tile multiples.  (Outside the
    schedule layer: its tiles are clamped to the sequence shape, see
    API.md.)"""
    from repro.kernels import flash_attention as _fa
    if interpret is None:
        interpret = _interpret_default()
    B, H, S, hd = q.shape
    KV, T = k.shape[1], k.shape[2]
    if KV != H:
        rep = H // KV
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    bq = min(bq, S)
    bk = min(bk, T)
    s_pad = ((S + bq - 1) // bq) * bq
    t_pad = ((T + bk - 1) // bk) * bk
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, s_pad - S), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, t_pad - T), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, t_pad - T), (0, 0)))
    if t_pad != T:
        # mask padded keys via a window/causal trick is insufficient for
        # non-causal; shift them out of range with -inf via key zeroing +
        # causal bound. Simplest robust: rely on causal masking when
        # S==T; otherwise require exact tiles.
        assert causal and s_pad == t_pad, "non-causal padding unsupported"
    out = _fa.flash_attention(qp, kp, vp, causal=causal, window=window,
                              bq=bq, bk=bk, interpret=interpret)
    return out[:, :, :S]


# Re-export oracles for test convenience.
reference = ref
