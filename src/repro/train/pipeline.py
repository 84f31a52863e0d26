"""GPipe-style pipeline parallelism over a mesh axis (the "pod" axis of
the multi-pod production mesh).

The L stacked layers are split into P = |axis| contiguous stages; layer
params shard their leading (layers) dim over the axis, so each pod holds
only its stage's weights.  M microbatches flow through the classic GPipe
schedule (T = M + P - 1 ticks); stage boundaries are one
``lax.ppermute`` per tick — autodiff transposes it to the reverse
permute, so ``jax.grad`` through :func:`pipeline_apply` yields the 1B1F
backward schedule for free.

Bubble fraction = (P-1)/(M+P-1); pick M >= 4P in production.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.distrib import mesh_utils


def pipeline_apply(layer_fn: Callable, stacked_params, x: jax.Array,
                   mesh: Mesh, axis: str = "pod",
                   microbatches: int | None = None) -> jax.Array:
    """Run ``layer_fn`` over L stacked layers, pipelined over ``axis``.

    layer_fn: (layer_params, x_mb) -> x_mb  (one layer, one microbatch)
    stacked_params: pytree with leading dim L (L % P == 0)
    x: (B, ...) global batch; B % microbatches == 0
    Returns (B, ...) with the same sharding as the input batch dim.
    """
    n_stage = mesh.shape[axis]
    leaves = jax.tree.leaves(stacked_params)
    L = leaves[0].shape[0]
    assert L % n_stage == 0, (L, n_stage)
    per_stage = L // n_stage
    M = microbatches or n_stage * 2
    B = x.shape[0]
    assert B % M == 0, (B, M)
    mb = B // M

    p_specs = jax.tree.map(lambda _: P(axis), stacked_params)
    other = tuple(a for a in mesh.axis_names if a != axis)

    def stage_body(params_local, xs):
        # params_local: (per_stage, ...) this stage's layers
        # xs: (M, mb, ...) microbatches, replicated over `axis`
        idx = lax.axis_index(axis)
        T = M + n_stage - 1
        xs = jnp.concatenate(
            [xs, jnp.zeros((n_stage - 1,) + xs.shape[1:], xs.dtype)], 0)

        def stage_fn(x_mb):
            def one(x, lp):
                return layer_fn(lp, x), None
            out, _ = lax.scan(one, x_mb, params_local)
            return out

        def tick(carry, t):
            buf, prev_out = carry
            # receive from the previous stage (stage 0 keeps its own feed)
            recv = lax.ppermute(
                prev_out, axis,
                perm=[(i, (i + 1) % n_stage) for i in range(n_stage)])
            feed_idx = jnp.clip(t, 0, T - 1)
            own = lax.dynamic_index_in_dim(xs, feed_idx, 0, keepdims=False)
            inp = jnp.where(idx == 0, own, recv)
            out = stage_fn(inp)
            # last stage writes its result for microbatch m = t - (P-1)
            write_m = jnp.clip(t - (n_stage - 1), 0, M - 1)
            do_write = (t >= n_stage - 1) & (idx == n_stage - 1)
            cur = lax.dynamic_index_in_dim(buf, write_m, 0, keepdims=False)
            new = jnp.where(do_write, out, cur)
            buf = lax.dynamic_update_index_in_dim(buf, new, write_m, 0)
            return (buf, out), None

        buf0 = jnp.zeros((M,) + xs.shape[1:], x.dtype)
        buf0 = lax.pcast(buf0, (axis,) + tuple(other), to="varying")
        prev0 = jnp.zeros(xs.shape[1:], x.dtype)
        prev0 = lax.pcast(prev0, (axis,) + tuple(other), to="varying")
        (buf, _), _ = lax.scan(tick, (buf0, prev0), jnp.arange(T))
        # broadcast the last stage's buffer to every stage (masked psum)
        buf = lax.psum(jnp.where(idx == n_stage - 1, buf, 0.0), axis)
        return buf

    xs = x.reshape((M, mb) + x.shape[1:])
    fn = jax.shard_map(
        stage_body, mesh=mesh,
        in_specs=(p_specs, P()),
        out_specs=P(),
        check_vma=False,
    )
    out = fn(stacked_params, xs)
    return out.reshape((B,) + out.shape[2:])


def bubble_fraction(n_stage: int, microbatches: int) -> float:
    return (n_stage - 1) / (microbatches + n_stage - 1)
