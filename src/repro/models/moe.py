"""Mixture-of-Experts FFN with sort-based capacity dispatch (EP-shardable).

Dispatch: tokens are replicated ``top_k`` times, sorted by assigned expert,
and packed into an (E, C, D) buffer (C = capacity per expert).  The expert
matmuls are dense einsums with E sharded over the ``model`` axis (expert
parallelism); GSPMD turns the gather/scatter across the data->expert layout
change into the all-to-all pair.  Overflowing tokens are dropped (weights
renormalized), standard capacity-factor semantics.

Router stats (load per expert, drop fraction) are returned for the
spectral-clustering integration (examples/moe_spectral_routing.py): the
expert co-activation matrix is clustering input for balanced expert
placement — the paper's pipeline consuming the LM substrate.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.config import ModelConfig
from repro.models.layers import _act
from repro.models.params import Spec


def moe_specs(cfg: ModelConfig, layers: int | None = None) -> dict:
    L = (layers,) if layers is not None else ()
    lax_ = ("layers",) if layers is not None else ()
    pd = cfg.param_dtype
    E, F = cfg.num_experts, cfg.expert_d_ff
    spec = {
        "router": Spec(L + (cfg.d_model, E), lax_ + ("embed", "experts"), pd,
                       init="normal", scale=0.02),
        "wi": Spec(L + (E, cfg.d_model, 2, F), lax_ + ("experts", "embed", None, "mlp"), pd),
        "wo": Spec(L + (E, F, cfg.d_model), lax_ + ("experts", "mlp", "embed"), pd),
    }
    if cfg.num_shared_experts:
        Fs = cfg.expert_d_ff * cfg.num_shared_experts
        spec["shared_wi"] = Spec(L + (cfg.d_model, 2, Fs), lax_ + ("embed", None, "mlp"), pd)
        spec["shared_wo"] = Spec(L + (Fs, cfg.d_model), lax_ + ("mlp", "embed"), pd)
    return spec


def _dispatch_indices(flat_expert: jax.Array, T: int, K: int, E: int, C: int):
    """Sort-based capacity packing: returns (buf_idx (E*C,) token ids with
    T as the pad sentinel, dest (T*K,), keep (T*K,), order (T*K,))."""
    order = jnp.argsort(flat_expert, stable=True)
    sorted_expert = flat_expert[order]
    counts = jnp.bincount(flat_expert, length=E)
    offsets = jnp.concatenate([jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]])
    slot_in_expert = jnp.arange(T * K) - offsets[sorted_expert]
    keep = slot_in_expert < C
    dest = jnp.where(keep, sorted_expert * C + slot_in_expert, E * C)
    src_token = order // K
    buf_idx = jnp.full((E * C + 1,), T, jnp.int32)
    buf_idx = buf_idx.at[dest].set(src_token.astype(jnp.int32))[: E * C]
    return buf_idx, dest, keep, order


def moe_ffn_ep_shard_map(x: jax.Array, p: dict, cfg: ModelConfig):
    """Explicit expert parallelism (the paper's map/shuffle/reduce, as a
    shard_map): tokens stay batch-sharded and are *replicated* over the
    model axis; each model column dispatches only to its own E/ep experts
    locally (no dispatch collective at all — the redundant router math is
    trivial), computes them, and a single psum over "model" combines the
    weighted expert outputs.  Requires E % ep == 0.

    vs. the GSPMD "gather" path, the all-gather of the full token matrix
    disappears: the only collective is one (T_loc, D) psum per layer.
    """
    from jax.sharding import PartitionSpec as P
    from repro.distrib import act_sharding

    mesh = act_sharding.current_mesh()
    B, S, D = x.shape
    T = B * S
    E, K = cfg.num_experts, cfg.top_k
    ep = mesh.shape["model"]
    assert E % ep == 0, (E, ep)
    E_loc = E // ep
    C = min(T, max(1, int(T * K * cfg.capacity_factor) // E))
    ba = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    def body(xt, router, wi, wo):
        # xt (T_loc, D) batch shard; wi/wo local expert slices (E_loc, ...)
        T_loc = xt.shape[0]
        col = lax.axis_index("model")
        logits = jnp.einsum("td,de->te", xt, router.astype(xt.dtype)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate, expert_idx = lax.top_k(probs, K)
        gate = gate / jnp.maximum(jnp.sum(gate, axis=-1, keepdims=True), 1e-9)
        C_loc = min(T_loc, max(1, int(T_loc * K * cfg.capacity_factor) // E))
        buf_idx, dest, keep, order = _dispatch_indices(
            expert_idx.reshape(-1), T_loc, K, E, C_loc)
        # my expert rows only
        my = lax.dynamic_slice(buf_idx, (col * E_loc * C_loc,), (E_loc * C_loc,))
        xt_pad = jnp.concatenate([xt, jnp.zeros((1, D), xt.dtype)], axis=0)
        dispatched = xt_pad[my].reshape(E_loc, C_loc, D)
        gu = jnp.einsum("ecd,edzf->eczf", dispatched, wi.astype(xt.dtype))
        h = _act(cfg.act)(gu[:, :, 0]) * gu[:, :, 1]
        out_buf = jnp.einsum("ecf,efd->ecd", h, wo.astype(xt.dtype))
        # local combine: weighted scatter-add of my experts' outputs
        flat_gate = gate.reshape(-1)[order]
        slot_gate = jnp.zeros((E * C_loc + 1,), jnp.float32).at[dest].set(
            jnp.where(keep, flat_gate, 0.0))[: E * C_loc]
        my_gate = lax.dynamic_slice(slot_gate, (col * E_loc * C_loc,),
                                    (E_loc * C_loc,))
        weighted = out_buf.reshape(E_loc * C_loc, D) * my_gate[:, None].astype(out_buf.dtype)
        partial = jnp.zeros((T_loc + 1, D), xt.dtype).at[my].add(weighted)[:T_loc]
        out = lax.psum(partial, "model")
        # aux (identical on every model column)
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(expert_idx[:, 0], E, dtype=jnp.float32), axis=0)
        lb = E * jnp.sum(me * ce)
        counts = jnp.bincount(expert_idx.reshape(-1), length=E)
        return out, lb, lax.psum(counts, ba) if ba else counts

    shard = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(ba, None), P(None, None), P("model", None, None, None),
                  P("model", None, None)),
        out_specs=(P(ba, None), P(), P()),
        check_vma=False,
    )
    out, lb, counts = shard(x.reshape(T, D), p["router"], p["wi"], p["wo"])
    out = out.reshape(B, S, D)
    if cfg.num_shared_experts:
        xt = x.reshape(T, D)
        gu_s = jnp.einsum("td,dzf->tzf", xt, p["shared_wi"].astype(x.dtype))
        hs = _act(cfg.act)(gu_s[:, 0]) * gu_s[:, 1]
        out = out + jnp.einsum("tf,fd->td", hs, p["shared_wo"].astype(x.dtype)).reshape(B, S, D)
    aux = {"lb_loss": lb, "expert_load": counts,
           "frac_dropped": jnp.zeros((), jnp.float32)}
    return out, aux


def moe_ffn(x: jax.Array, p: dict, cfg: ModelConfig):
    """x: (B, S, D) -> (B, S, D), aux dict with router stats + load-balance loss."""
    from repro.distrib import act_sharding
    if cfg.moe_impl == "ep_shard_map" and act_sharding.current_mesh() is not None:
        return moe_ffn_ep_shard_map(x, p, cfg)
    B, S, D = x.shape
    T = B * S
    E, K = cfg.num_experts, cfg.top_k
    # capacity per expert; capped at T (an expert can never receive more
    # than every token), which also makes small decode batches drop-free
    C = min(T, max(1, int(T * K * cfg.capacity_factor) // E))
    xt = x.reshape(T, D)

    logits = jnp.einsum("td,de->te", xt, p["router"].astype(x.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, expert_idx = lax.top_k(probs, K)                     # (T, K)
    gate = gate / jnp.maximum(jnp.sum(gate, axis=-1, keepdims=True), 1e-9)

    # ---- pack: sort the T*K assignments by expert, take first C per expert
    flat_expert = expert_idx.reshape(-1)                       # (T*K,)
    order = jnp.argsort(flat_expert, stable=True)              # (T*K,)
    sorted_expert = flat_expert[order]
    counts = jnp.bincount(flat_expert, length=E)               # (E,)
    offsets = jnp.concatenate([jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]])
    slot_in_expert = jnp.arange(T * K) - offsets[sorted_expert]
    keep = slot_in_expert < C
    # destination slot in the (E*C) buffer; dropped tokens go to a trash slot
    dest = jnp.where(keep, sorted_expert * C + slot_in_expert, E * C)
    src_token = order // K                                      # token id per sorted slot

    buf_idx = jnp.full((E * C + 1,), T, jnp.int32)              # T = pad token row
    buf_idx = buf_idx.at[dest].set(src_token.astype(jnp.int32))
    buf_idx = buf_idx[: E * C]
    xt_pad = jnp.concatenate([xt, jnp.zeros((1, D), xt.dtype)], axis=0)
    dispatched = xt_pad[buf_idx].reshape(E, C, D)

    # ---- expert compute: E over "model" when divisible; the capacity dim
    # shards over the data axes either way, so expert matmuls use the FULL
    # mesh (without this, E < mesh width leaves the data axis idle and
    # replicates expert FLOPs |data|-fold)
    from repro.distrib import act_sharding
    ba = act_sharding.batch_axes_in_mesh()
    espec = {0: "model", 1: ba or None}   # E over model when divisible
    dispatched = act_sharding.constrain_dims(dispatched, espec)
    gu = jnp.einsum("ecd,edzf->eczf", dispatched, p["wi"].astype(x.dtype))
    h = _act(cfg.act)(gu[:, :, 0]) * gu[:, :, 1]                # (E, C, F)
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["wo"].astype(x.dtype))
    out_buf = act_sharding.constrain_dims(out_buf, espec)

    # ---- combine: scatter-add back with gate weights
    flat_gate = gate.reshape(-1)[order]                         # aligned with sorted slots
    slot_gate = jnp.zeros((E * C + 1,), jnp.float32).at[dest].set(
        jnp.where(keep, flat_gate, 0.0))[: E * C]
    weighted = out_buf.reshape(E * C, D) * slot_gate[:, None].astype(out_buf.dtype)
    out = jnp.zeros((T + 1, D), x.dtype).at[buf_idx].add(weighted)[:T]

    if cfg.num_shared_experts:
        gu_s = jnp.einsum("td,dzf->tzf", xt, p["shared_wi"].astype(x.dtype))
        hs = _act(cfg.act)(gu_s[:, 0]) * gu_s[:, 1]
        out = out + jnp.einsum("tf,fd->td", hs, p["shared_wo"].astype(x.dtype))

    # ---- aux: load-balance loss (Switch) + stats for spectral routing
    me = jnp.mean(probs, axis=0)                                # (E,)
    ce = jnp.mean(jax.nn.one_hot(expert_idx[:, 0], E, dtype=jnp.float32), axis=0)
    lb_loss = E * jnp.sum(me * ce)
    frac_dropped = 1.0 - jnp.sum(jnp.where(keep, 1.0, 0.0)) / (T * K)
    aux = {"lb_loss": lb_loss, "expert_load": counts, "frac_dropped": frac_dropped}
    return out.reshape(B, S, D), aux
