"""Phase 2 of the paper: block Lanczos for the k smallest eigenvectors
(Alg. 4.3).

The product ``L @ V`` is the distributed hot spot: the caller passes a
``matmat`` operator (row-sharded symmetric operator from
``core.similarity`` / ``core.laplacian``), and the recurrence itself runs
on replicated blocks, exactly the paper's "move the vector to the data"
split.  An operator given as a ``jax.tree_util.Partial`` over arrays runs
through one compiled loop per shape; a closure is traced again on every
call (:func:`block_run`).

The recurrence is block-tridiagonal on ``b`` vectors at once, so every
step costs ONE pass over the matrix (one ``matmat``) amortized across the
whole block, instead of one pass per vector (Jin & JaJa 2018).  The
paper's single-vector Lanczos is the same recurrence at ``b = 1``.

Deviations from the paper, both for correctness:
  * full reorthogonalization (CGS2) against the whole basis: plain
    Lanczos loses orthogonality in finite precision and returns wrong
    small eigenvectors;
  * the iteration runs on the *shifted* operator A = 2I - L_sym
    (``core.laplacian``), so extremal (largest) Ritz pairs of A are the
    smallest of L_sym.

The state is an explicit pytree so the launcher can checkpoint/restore
the iteration mid-run (fault tolerance; the paper gets this from Hadoop
task re-execution).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.precision import matmul


@jax.tree_util.register_pytree_node_class
@dataclass
class BlockLanczosState:
    """Checkpointable block-Lanczos iteration state.

    ``block_size`` b is static; after ``step`` completed block steps the
    first ``(step + 1) * b`` rows of ``V`` hold the orthonormal basis.
    """

    step: jax.Array    # scalar int32: number of completed block steps
    V: jax.Array       # ((s+1)*b, n) basis rows; blocks > step are zero
    A: jax.Array       # (s, b, b) block-diagonal of T; symmetric blocks
    B: jax.Array       # (s+1, b, b) subdiagonal blocks of T; B[0] == 0
    block_size: int    # static

    def tree_flatten(self):
        return (self.step, self.V, self.A, self.B), (self.block_size,)

    @staticmethod
    def tree_unflatten(aux, children):
        return BlockLanczosState(*children, block_size=aux[0])


def qr_pos(U: jax.Array, eps: float = 1e-8) -> tuple[jax.Array, jax.Array]:
    """Reduced QR with non-negative R diagonal; (near-)dependent columns
    are zeroed instead of admitting junk directions into the basis: a
    dead direction decouples from T and lands at the spectrum floor."""
    Q, R = jnp.linalg.qr(U)
    d = jnp.diagonal(R)
    sgn = jnp.where(d < 0, -1.0, 1.0).astype(U.dtype)
    Q = Q * sgn[None, :]
    R = R * sgn[:, None]
    keep = (jnp.diagonal(R) > eps).astype(U.dtype)
    return Q * keep[None, :], R * keep[:, None]


def init_block_state(n: int, num_steps: int, key: jax.Array, block_size: int,
                     V0: jax.Array | None = None,
                     dtype=jnp.float32) -> BlockLanczosState:
    """Random (or caller-supplied) orthonormal (b, n) start block."""
    if V0 is None:
        V0 = jax.random.normal(key, (block_size, n), dtype)
    return _start_state(V0.astype(dtype), num_steps)


def _zero_state(Q: jax.Array, num_steps: int) -> BlockLanczosState:
    """The state before any step, with the orthonormal (n, b) ``Q`` as
    its first basis block."""
    n, b = Q.shape
    dtype = Q.dtype
    V = jnp.zeros(((num_steps + 1) * b, n), dtype).at[:b].set(Q.T)
    return BlockLanczosState(
        step=jnp.zeros((), jnp.int32),
        V=V,
        A=jnp.zeros((num_steps, b, b), dtype),
        B=jnp.zeros((num_steps + 1, b, b), dtype),
        block_size=b,
    )


# One program for the start block's QR and the zeroed state: run eagerly,
# its dozen dispatches cost the paper graph's eigensolve about 11 ms a job
# on a TPU v5e (PERF.md, section 6).
@functools.partial(jax.jit, static_argnums=1)
def _start_state(V0: jax.Array, num_steps: int) -> BlockLanczosState:
    Q, _ = qr_pos(V0.T)
    return _zero_state(Q, num_steps)


@functools.partial(jax.jit, static_argnums=2)
def _folded_state(Q0: jax.Array, W0: jax.Array,
                  num_steps: int) -> BlockLanczosState:
    """The state after the first block step, taken from a start pair
    ``(Q0, W0 = A Q0)`` whose product came with no pass of its own."""
    return _block_step_update(_zero_state(Q0, num_steps), W0)


def _current_block(state: BlockLanczosState) -> jax.Array:
    """The (b, n) basis block the next step multiplies the operator by."""
    b = state.block_size
    _, n = state.V.shape
    return lax.dynamic_slice(state.V, (state.step * b, 0), (b, n))


def _block_step_update(state: BlockLanczosState,
                       W: jax.Array) -> BlockLanczosState:
    """Everything in a block step AFTER the matrix pass: given
    ``W = A @ Vj.T`` for the current block, orthogonalize and append the
    next block.  Split out from :func:`_block_step_body` so host-streaming
    operators can run the matmat as plain Python between two jitted halves
    (:func:`block_run_host`) instead of through ``pure_callback``."""
    j = state.step
    b = state.block_size
    rows, n = state.V.shape
    Vj = lax.dynamic_slice(state.V, (j * b, 0), (b, n))          # (b, n)
    Vp = lax.dynamic_slice(state.V, (jnp.maximum(j - 1, 0) * b, 0), (b, n))
    Vp = jnp.where(j > 0, 1.0, 0.0).astype(Vp.dtype) * Vp
    Bj = lax.dynamic_slice(state.B, (j, 0, 0), (1, b, b))[0]     # (b, b)

    W = W.astype(state.V.dtype) - matmul(Vp.T, Bj.T)
    Aj = matmul(Vj, W)                                           # (b, b)
    Aj = 0.5 * (Aj + Aj.T)          # symmetric operator -> symmetric block
    W = W - matmul(Vj.T, Aj)
    # Full reorthogonalization against the whole block basis, "twice is
    # enough" (CGS2); the row mask limits it to the filled blocks.
    mask = (jnp.arange(rows) < (j + 1) * b).astype(W.dtype)
    for _ in range(2):
        C = matmul(state.V, W) * mask[:, None]
        W = W - matmul(state.V.T, C)
    Qn, R = qr_pos(W)
    return BlockLanczosState(
        step=j + 1,
        V=lax.dynamic_update_slice(state.V, Qn.T, ((j + 1) * b, 0)),
        A=lax.dynamic_update_slice(
            state.A, Aj[None].astype(state.A.dtype), (j, 0, 0)),
        B=lax.dynamic_update_slice(
            state.B, R[None].astype(state.B.dtype), (j + 1, 0, 0)),
        block_size=b,
    )


def _block_step_body(matmat: Callable,
                     state: BlockLanczosState) -> BlockLanczosState:
    W = matmat(_current_block(state).T)                          # (n, b)
    return _block_step_update(state, W)


def _block_steps(matmat: Callable, state: BlockLanczosState,
                 num_iters: int) -> BlockLanczosState:
    def body(_, s):
        return _block_step_body(matmat, s)
    return lax.fori_loop(0, num_iters, body, state)


# The recurrence with the operator as an ARGUMENT: a
# ``jax.tree_util.Partial`` operator flattens to its arrays, so every
# operator of the same shapes shares one compiled loop, and the cache keys
# on shapes alone (never on the operator's arrays, which it must not keep
# alive).  A plain closure cannot go through here: as a static argument it
# would key the cache on each fit's new closure and pin its matrix.
_block_steps_jit = jax.jit(_block_steps, static_argnums=2)


def _advance(matmat: Callable, state: BlockLanczosState,
             num_iters: int) -> BlockLanczosState:
    """``num_iters`` block steps, synchronized: through the shared jitted
    loop for a :class:`jax.tree_util.Partial` operator, else an eager
    ``fori_loop`` traced again on every call."""
    if isinstance(matmat, jax.tree_util.Partial):
        out = _block_steps_jit(matmat, state, num_iters)
    else:
        out = _block_steps(matmat, state, num_iters)
    return jax.block_until_ready(out)


def block_run(matmat: Callable, state: BlockLanczosState,
              num_iters: int) -> BlockLanczosState:
    """Advance the block recurrence ``num_iters`` block steps — each step
    is ONE matrix pass (one matmat of width b).  Checkpoint-friendly.

    A ``matmat`` given as a :class:`jax.tree_util.Partial` of a
    module-level function over arrays (what
    :func:`~repro.core.laplacian.make_dense_operator` returns) runs
    through one compiled loop per shape that every later fit reuses; a
    plain closure is traced again on every call.

    The returned state is synchronized (``block_until_ready``): ``matmat``
    may embed a host callback, and returning while that computation is
    still in flight lets the caller's op-by-op dispatch race the callback
    on the CPU runtime's single work queue — a deadlock, not just a
    slowdown.  The caller consumes the state immediately, so the barrier
    costs nothing.  (Host-streaming operators should prefer
    :func:`block_run_host`, which keeps the matrix pass out of the traced
    computation entirely.)"""
    return _advance(matmat, state, num_iters)


def _block_step_advance(state: BlockLanczosState, W: jax.Array
                        ) -> tuple[BlockLanczosState, jax.Array]:
    """One host-driver dispatch: apply the post-matmat half of a step AND
    slice out the next block to multiply — fusing what would otherwise be
    two jitted calls per iteration (the slice is trivial next to the CGS2
    reorthogonalization it piggybacks on)."""
    new = _block_step_update(state, W)
    return new, _current_block(new)


_current_block_jit = jax.jit(_current_block)
_block_step_update_jit = jax.jit(_block_step_update)
_block_step_advance_jit = jax.jit(_block_step_advance)


def block_run_host(host_matmat: Callable, state: BlockLanczosState,
                   num_iters: int) -> BlockLanczosState:
    """:func:`block_run` for HOST-STREAMING operators: ``host_matmat`` is
    plain host code (numpy (n, b) -> (n, b)) invoked between jitted step
    updates, NOT traced into the computation.

    Rationale: embedding the host matmat via ``jax.pure_callback`` puts
    the Python callback on the CPU runtime's worker pool; on small hosts
    that pool has ONE thread, and the callback machinery's own
    ``device_put`` of the operands can queue a deferred copy behind the
    very computation that is blocked waiting for the callback — a
    self-deadlock (observed repeatedly under the async engine).  Driving
    the step from Python keeps the runtime free while the host pass runs,
    and the numerics are unchanged: the step halves execute the exact
    same primitives the fused step body traces around the callback."""
    Vj = np.asarray(_current_block_jit(state))                   # (b, n)
    for _ in range(num_iters):
        W = host_matmat(np.ascontiguousarray(Vj.T))              # (n, b)
        state, nxt = _block_step_advance_jit(state, jnp.asarray(W))
        Vj = np.asarray(nxt)
    return jax.block_until_ready(state)


def block_lanczos(matmat: Callable, n: int, num_steps: int, key: jax.Array,
                  block_size: int = 8, dtype=jnp.float32,
                  V0: jax.Array | None = None,
                  host_matmat: Callable | None = None,
                  start: tuple | None = None) -> BlockLanczosState:
    """``num_steps`` block steps from a random start block, or from
    ``start = (Q0, W0)``: an orthonormal (n, b) block and ``A Q0``
    already computed, so the first step makes no pass and the loop runs
    the other ``num_steps - 1``."""
    if start is None:
        state = init_block_state(n, num_steps, key, block_size, V0=V0,
                                 dtype=dtype)
        iters = num_steps
    else:
        Q0, W0 = start
        state = _folded_state(Q0.astype(dtype), W0.astype(dtype), num_steps)
        iters = num_steps - 1
    if host_matmat is not None:
        return block_run_host(host_matmat, state, iters)
    return block_run(matmat, state, iters)


def _ritz_vectors(V: jax.Array, evecs: jax.Array) -> jax.Array:
    """The Ritz vectors (n, s*b): the basis rows' combinations ``evecs``."""
    return matmul(V[: evecs.shape[0]].T, evecs)


def block_ritz_pairs(state: BlockLanczosState) -> tuple[jax.Array, jax.Array]:
    """Ritz values (ascending) and vectors (n, s*b) of the operator.

    The block tridiagonal T is assembled and solved on the host in
    float64: the TPU's Jacobi ``eigh`` stops at a relative tolerance of
    1e-6, which left the paper graph's Ritz pairs off their Rayleigh-Ritz
    conditions by up to 3.2e-5 on a TPU v5e (PERF.md, section 6).  T is
    a few hundred rows at most, so the host's solve costs milliseconds;
    the Ritz vectors are one product on the device."""
    A = np.asarray(state.A, np.float64)
    B = np.asarray(state.B, np.float64)
    s, b, _ = A.shape
    T = np.zeros((s * b, s * b))
    for j in range(s):
        T[j * b:(j + 1) * b, j * b:(j + 1) * b] = A[j]
        if j + 1 < s:
            T[(j + 1) * b:(j + 2) * b, j * b:(j + 1) * b] = B[j + 1]
            T[j * b:(j + 1) * b, (j + 1) * b:(j + 2) * b] = B[j + 1].T
    evals, evecs = np.linalg.eigh(T)             # ascending
    dtype = state.V.dtype
    return (jnp.asarray(evals, dtype),
            _ritz_vectors(state.V, jnp.asarray(evecs, dtype)))


def block_topk_of_shifted(state: BlockLanczosState, k: int,
                          shift: float = 2.0) -> tuple[jax.Array, jax.Array]:
    """k smallest eigenpairs of L given block Lanczos ran on
    A = shift*I - L.  Returns (eigvals ascending (k,), eigvecs (n, k),
    unit columns)."""
    evals_A, vecs = block_ritz_pairs(state)
    # largest of A  <->  smallest of L
    topk = vecs[:, -k:][:, ::-1]
    vals_L = (shift - evals_A[-k:])[::-1]
    norms = jnp.linalg.norm(topk, axis=0, keepdims=True)
    topk = topk / jnp.maximum(norms, 1e-12)
    return vals_L, topk


def residuals(matmat: Callable, vals: jax.Array, vecs: jax.Array,
              shift: float | None = None) -> jax.Array:
    """||Op v - lambda v|| per Ritz pair (convergence diagnostics)."""
    lam_op = (shift - vals) if shift is not None else vals
    return jnp.linalg.norm(matmat(vecs) - vecs * lam_op[None, :], axis=0)
