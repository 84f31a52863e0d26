"""Pallas TPU flash attention (beyond-paper optimization for the LM cells).

The roofline analysis (EXPERIMENTS.md §Perf) shows every *_32k attention
cell is memory-bound on XLA's chunked online-softmax: the mask/exp/reduce
passes materialize f32 score tiles in HBM ~4x per (q,k) block.  A fused
kernel keeps the (bq, bk) score tile in VMEM: HBM traffic collapses to
q/k/v reads + one output write —

    bytes_xla   ~= S*T*(4 passes)*4B      per (batch, head)
    bytes_flash ~= (S + 2T)*hd*2B + S*hd*2B

For S=T=32k, hd=128: ~17 GB -> ~0.03 GB per (batch, head): the memory
term drops below the compute term, i.e. attention becomes MXU-bound.

Grid: (batch*kv_heads*q_groups, S/bq); the kv loop runs *inside* the
kernel body (fori over T/bk) with the online-softmax state in VMEM
registers.  Causal + local-window masking is applied per tile; fully
masked tiles are skipped by bounding the fori range (the window start /
causal end are affine in the q-block index, so the trip bounds stay SPMD-
uniform).  Validated against ref.flash_attention on CPU in interpret mode
(tests/test_kernels_flash.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, bq, bk, seq_k, scale,
                  causal, window):
    qi = pl.program_id(1)
    q = q_ref[0]                       # (bq, hd); leading block dim is 1
    hd = q.shape[-1]

    q0 = qi * bq                       # first query position of this block
    # kv tile range: causal => tiles with t0 <= q_end; window => t_end >
    # q0 - window (affine bounds, identical structure on every program)
    hi = (q0 + bq + bk - 1) // bk if causal else seq_k // bk
    lo = jnp.maximum(0, q0 - window + 1) // bk if window > 0 else 0

    def body(ti, acc):
        m, l, o = acc
        t0 = ti * bk
        k = k_ref[0, pl.ds(t0, bk), :]                            # (bk, hd)
        v = v_ref[0, pl.ds(t0, bk), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = t0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        ok = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            ok = ok & (kpos <= qpos)
        if window > 0:
            ok = ok & (qpos - kpos < window)
        s = jnp.where(ok, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=1)
        o_new = o * corr[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, o_new

    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    o0 = jnp.zeros((bq, hd), jnp.float32)
    m, l, o = jax.lax.fori_loop(lo, hi, body, (m0, l0, o0))
    o_ref[0] = (o / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "bq", "bk", "causal", "window", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    scale: float | None = None, causal: bool = True,
                    window: int = -1, bq: int = 256, bk: int = 256,
                    interpret: bool = True) -> jax.Array:
    """q: (B, H, S, hd); k/v: (B, H, T, hd) (kv heads pre-broadcast).
    S % bq == 0 and T % bk == 0 (use ops.flash_attention for padding)."""
    B, H, S, hd = q.shape
    T = k.shape[2]
    assert S % bq == 0 and T % bk == 0, (S, T, bq, bk)
    scale = scale if scale is not None else 1.0 / (hd ** 0.5)
    qf = q.reshape(B * H, S, hd)
    kf = k.reshape(B * H, T, hd)
    vf = v.reshape(B * H, T, hd)
    kernel = functools.partial(_flash_kernel, bq=bq, bk=bk, seq_k=T,
                               scale=scale, causal=causal, window=window)
    out = pl.pallas_call(
        kernel,
        grid=(B * H, S // bq),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, T, hd), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, T, hd), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, S, hd)
