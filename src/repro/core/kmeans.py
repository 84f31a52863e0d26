"""Phase 3 of the paper: parallel k-means (Alg. in §4.3.3).

map  = assign each point to the nearest center        -> per-device argmin
reduce = per-cluster coordinate sums -> new centers   -> jax.lax.psum

Points are row-sharded; centers are replicated (the paper's "center file"
read by every worker).  Empty clusters keep their previous center.  A
k-means++ initializer replaces the paper's unspecified init (standard
practice; plain random init frequently collapses on spectral embeddings).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.seeding import kmeans_plusplus_init  # noqa: F401  (shared
# D^2-sampling seeder, re-exported: callers historically import it from here)
from repro.core.similarity import pairwise_sq_dists
from repro.distrib import mesh_utils
from repro.precision import matmul


@jax.tree_util.register_pytree_node_class
@dataclass
class KMeansState:
    """Checkpointable k-means iteration state (the paper's "center file")."""
    it: jax.Array        # scalar int32
    centers: jax.Array   # (k, dim) replicated
    shift: jax.Array     # scalar: last center movement (convergence signal)

    def tree_flatten(self):
        return (self.it, self.centers, self.shift), None

    @staticmethod
    def tree_unflatten(aux, children):
        return KMeansState(*children)


def normalize_rows(Z: jax.Array, eps: float = 1e-12) -> jax.Array:
    """Alg. 4.1 step 5: Y = Z with unit-norm rows."""
    norms = jnp.linalg.norm(Z, axis=1, keepdims=True)
    return Z / jnp.maximum(norms, eps)


def assign(y: jax.Array, centers: jax.Array) -> jax.Array:
    """Nearest-center index per point (the paper's map function)."""
    return jnp.argmin(pairwise_sq_dists(y, centers), axis=1)


def _update(y, valid, centers):
    """One Lloyd step on a local block; caller psums (sums, counts)."""
    k = centers.shape[0]
    d2 = pairwise_sq_dists(y, centers)
    idx = jnp.argmin(d2, axis=1)
    onehot = jax.nn.one_hot(idx, k, dtype=y.dtype) * valid[:, None]
    sums = matmul(onehot.T, y)                # (k, dim)
    counts = jnp.sum(onehot, axis=0)          # (k,)
    inertia = jnp.sum(jnp.min(d2, axis=1) * valid)
    return sums, counts, inertia


def lloyd_step(y: jax.Array, valid: jax.Array, state: KMeansState) -> KMeansState:
    """Single-device Lloyd iteration (reference; also the per-shard body)."""
    sums, counts, _ = _update(y, valid, state.centers)
    new = jnp.where(counts[:, None] > 0, sums / jnp.maximum(counts[:, None], 1), state.centers)
    return KMeansState(it=state.it + 1, centers=new,
                       shift=jnp.linalg.norm(new - state.centers))


def kmeans(y: jax.Array, k: int, key: jax.Array, iters: int = 50,
            centers0: jax.Array | None = None) -> tuple[jax.Array, jax.Array]:
    """Reference single-device k-means. Returns (labels, centers)."""
    centers = centers0 if centers0 is not None else kmeans_plusplus_init(y, k, key)
    valid = jnp.ones((y.shape[0],), y.dtype)
    state = KMeansState(it=jnp.zeros((), jnp.int32), centers=centers,
                        shift=jnp.asarray(jnp.inf, y.dtype))

    def body(_, s):
        return lloyd_step(y, valid, s)

    state = lax.fori_loop(0, iters, body, state)
    return assign(y, state.centers), state.centers


def minibatch_kmeans(y: jax.Array, valid: jax.Array, k: int, key: jax.Array,
                     iters: int = 50, batch: int = 256,
                     centers0: jax.Array | None = None
                     ) -> tuple[jax.Array, jax.Array]:
    """Mini-batch Lloyd (Sculley-style per-center learning rates).

    For large ``n`` a full Lloyd pass per round is the dominant cost; each
    round here touches only ``batch`` sampled points, with center c moving
    toward its batch mean at rate (batch count)/(lifetime count).  ``valid``
    weights the sampling so padding rows are never drawn.  Returns
    ``(labels, centers)`` with labels from one final full assignment.
    """
    n = y.shape[0]
    batch = int(min(batch, n))
    key, init_key = jax.random.split(key)
    if centers0 is None:
        centers0 = kmeans_plusplus_init(y, k, init_key, weights=valid)
    p = valid / jnp.maximum(jnp.sum(valid), 1.0)

    def body(_, carry):
        centers, counts, key = carry
        key, sub = jax.random.split(key)
        idx = jax.random.choice(sub, n, (batch,), replace=True, p=p)
        yb = y[idx]
        a = jnp.argmin(pairwise_sq_dists(yb, centers), axis=1)
        onehot = jax.nn.one_hot(a, k, dtype=y.dtype)
        bc = jnp.sum(onehot, axis=0)                 # (k,) batch counts
        bmean = matmul(onehot.T, yb) / jnp.maximum(bc[:, None], 1.0)
        counts = counts + bc
        lr = bc / jnp.maximum(counts, 1.0)
        centers = jnp.where(bc[:, None] > 0,
                            centers + lr[:, None] * (bmean - centers), centers)
        return centers, counts, key

    centers, _, _ = lax.fori_loop(
        0, iters, body, (centers0, jnp.zeros((k,), y.dtype), key))
    return assign(y, centers), centers


def distributed_lloyd_step(y_sharded: jax.Array, valid: jax.Array,
                           state: KMeansState, mesh: Mesh) -> KMeansState:
    """One MapReduce round: shard-local assign+sum, psum reduce, new centers."""
    axes = mesh_utils.flat_axes(mesh)
    axis = axes[0] if len(axes) == 1 else axes

    def body(y_local, valid_local, centers):
        sums, counts, inertia = _update(y_local, valid_local, centers)
        sums = lax.psum(sums, axis)
        counts = lax.psum(counts, axis)
        return sums, counts

    shard = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axes, None), P(axes), P()),
        out_specs=(P(), P()),
    )
    sums, counts = shard(y_sharded, valid, state.centers)
    new = jnp.where(counts[:, None] > 0,
                    sums / jnp.maximum(counts[:, None], 1), state.centers)
    return KMeansState(it=state.it + 1, centers=new,
                       shift=jnp.linalg.norm(new - state.centers))


@partial(jax.jit, static_argnames=("k", "mesh", "iters", "tol"))
def distributed_kmeans(y_sharded: jax.Array, valid: jax.Array, k: int,
                       key: jax.Array, mesh: Mesh, iters: int = 50,
                       centers0: jax.Array | None = None,
                       tol: float = 1e-6) -> tuple[jax.Array, KMeansState]:
    """Paper §4.3.3 on a device mesh. ``y_sharded`` is (n_pad, dim) row-sharded,
    ``valid`` the padding mask. Runs a fixed ``iters`` rounds with early-exit
    semantics folded into the state (shift < tol keeps centers fixed).

    Jitted with ``k``, ``mesh``, ``iters`` and ``tol`` static (a ``Mesh``
    hashes by value), so every later fit of the same shapes reuses one
    program: the seeding, the rounds and the final assignment."""
    if centers0 is None:
        # ++-init needs a global view; the embedding (n, k) is small (the
        # paper also keeps centers in a single HBase "center file").
        centers0 = kmeans_plusplus_init(
            jnp.asarray(y_sharded), k, key, weights=valid)
    state = KMeansState(it=jnp.zeros((), jnp.int32), centers=centers0,
                        shift=jnp.asarray(jnp.inf, y_sharded.dtype))

    def body(_, s):
        nxt = distributed_lloyd_step(y_sharded, valid, s, mesh)
        frozen = s.shift < tol
        centers = jnp.where(frozen, s.centers, nxt.centers)
        shift = jnp.where(frozen, s.shift, nxt.shift)
        return KMeansState(it=nxt.it, centers=centers, shift=shift)

    state = lax.fori_loop(0, iters, body, state)
    labels = assign(jnp.asarray(y_sharded), state.centers)
    return labels, state
