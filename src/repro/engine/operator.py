"""Sharded-CSR graph + the streaming :class:`NormalizedOperator` reduce
tasks build on top of it.

The whole point of the engine: the similarity graph exists only as
per-row-range CSR shards inside a (possibly spilled) shard store, and the
eigensolve consumes it through a **matmat** that *streams* the shards —
one shard resident at a time, never a dense (n, n) anything, and each
shard pulled once per (n, b) block rather than once per vector.  The
host-side stream is lifted into the jitted eigensolver loops with
``jax.pure_callback``, so every registry backend (``lanczos``,
``block-lanczos``, ``chebdav``, ``eigh``) works unchanged.

Asynchrony (PR 8): shard fetches run on a pool of up to
``plan.prefetch_depth`` readahead workers (the store is thread-safe, so
spill-reloads overlap each other AND the compute), and on accelerator
backends the per-shard CSR product runs as a jitted device segment-sum —
shard c+1's fetch/upload overlaps shard c's multiply, with the
single-pass host scatter kept as the CPU fallback.
"""
from __future__ import annotations

import threading
import weakref
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.cluster.operator import NormalizedOperator
from repro.engine.plan import JobPlan
from repro.engine.store import ShardStore

_SCATTER_IMPLS = ("auto", "device", "host", "loop")


def _shutdown_pool(pool: ThreadPoolExecutor) -> None:
    """Finalizer-safe pool shutdown: joins the workers unless it is
    ITSELF running on one (a worker can drop a graph's last reference;
    self-join would raise and strand the pool)."""
    pool.shutdown(wait=threading.current_thread() not in pool._threads)


def scatter_rows(Y: np.ndarray, rows: np.ndarray,
                 prods: np.ndarray) -> None:
    """Accumulate ``prods`` (nnz, b) into ``Y`` (nrows, b) by row id, in
    ONE pass over ``prods`` (the old path ran b ``np.bincount`` passes —
    one per column).  CSR-derived ``rows`` are non-decreasing, so the
    fast path reduces each row's contiguous run with ``np.add.reduceat``;
    unsorted ids fall back to a single ``np.add.at`` scatter."""
    if len(rows) == 0:
        return
    if np.all(rows[:-1] <= rows[1:]):
        starts = np.flatnonzero(np.r_[True, np.diff(rows) != 0])
        Y[rows[starts]] += np.add.reduceat(prods, starts, axis=0)
    else:
        np.add.at(Y, rows, prods)


def _bincount_loop_rows(rows: np.ndarray, prods: np.ndarray,
                        nrows: int) -> np.ndarray:
    """The pre-async per-column scatter — b ``np.bincount`` passes over
    ``prods``.  Kept verbatim as the parity oracle and the "PR 7
    sequential engine" benchmark baseline (``matmat_impl="loop"``)."""
    Y = np.empty((nrows, prods.shape[1]), np.float32)
    for j in range(prods.shape[1]):
        Y[:, j] = np.bincount(rows, weights=prods[:, j], minlength=nrows)
    return Y


@partial(jax.jit, static_argnames=("nrows",))
def _csr_segment_matmat(data: jax.Array, indices: jax.Array,
                        rows: jax.Array, V: jax.Array,
                        nrows: int) -> jax.Array:
    """Device-side shard product: gather V rows, scale, segment-sum by
    local row id.  Padding entries carry data == 0, so they contribute
    nothing wherever their (clipped) indices land."""
    prods = data[:, None] * jnp.take(V, indices, axis=0)
    return jax.ops.segment_sum(prods, rows, num_segments=nrows)


def _pad_nnz(a: np.ndarray, target: int) -> np.ndarray:
    return np.pad(a, (0, target - len(a))) if len(a) < target else a


@dataclass
class ShardedCSRGraph:
    """Symmetrized top-t similarity graph as per-row-range CSR shards.

    ``store`` holds one ``shard/<c>`` entry per row range (indptr/indices/
    data, see the store docstring); ``deg`` is the full degree vector
    (small: (n,)), accumulated by the reduce tasks.
    """

    store: ShardStore
    plan: JobPlan
    deg: np.ndarray                      # (n,) float32 row sums of S
    nnz: int
    stats: Dict = field(default_factory=dict)
    # per-shard scatter implementation: "auto" routes to the jitted
    # device segment-sum on accelerators and the single-pass host scatter
    # on CPU; "loop" pins the pre-async per-column bincount reference
    matmat_impl: str = field(default="auto", init=False, compare=False)
    # readahead pool: up to plan.prefetch_depth workers fetch upcoming
    # shards from the (thread-safe) store while the current one multiplies
    _prefetch_pool: Optional[ThreadPoolExecutor] = field(
        default=None, init=False, repr=False, compare=False)
    _pool_finalizer: object = field(default=None, init=False, repr=False,
                                    compare=False)
    # cross-call warm start: futures for the NEXT matmat's first window
    # of shards, submitted as the previous call returns (see matmat
    # docstring)
    _warm: Optional[Dict[int, object]] = field(default=None, init=False,
                                               repr=False, compare=False)

    @property
    def n(self) -> int:
        return self.plan.n

    def shard(self, c: int) -> Dict[str, np.ndarray]:
        return self.store.get(f"shard/{c}")

    def _submit_fetch(self, pool: ThreadPoolExecutor, c: int):
        """Queue a background fetch of shard ``c``.  The work item closes
        over the STORE, not the graph: a submitted bound ``self.shard``
        would let a prefetch worker hold the graph's last reference, and
        the pool finalizer firing on its own worker cannot join it."""
        return pool.submit(self.store.get, f"shard/{c}")

    def _drain_prefetch(self) -> None:
        """Settle the in-flight warm-start gets (a failed warm fetch only
        loses warmth; matmat consumes every window future it submits)."""
        warm, self._warm = self._warm, None
        for fut in (warm or {}).values():
            try:
                fut.result()
            except Exception:
                pass

    def prewarm(self) -> None:
        """Start fetching the first ``prefetch_depth`` shards in the
        background, so the FIRST matmat finds its window already loaded.
        ``build_graph`` calls this as the build finishes: the fetches
        overlap the eigensolver's own warm-up (start-block QR, jit entry)
        instead of stalling its first pass.  Idempotent."""
        if self._warm is None:
            pool = self._pool()
            depth = max(1, int(getattr(self.plan, "prefetch_depth", 1)))
            nshards = len(self.plan.ranges)
            self._warm = {c: self._submit_fetch(pool, c)
                          for c in range(min(depth, nshards))}

    def stats_snapshot(self) -> Dict:
        """Static stage counters + live store counters (the store keeps
        spilling/loading while consumers stream the shards) — the one
        merge every stats reporter uses."""
        self._drain_prefetch()
        self.store.flush()          # settle async spill accounting
        snap = dict(self.stats, nnz=self.nnz,
                    spilled_shards=len(self.store.spilled_keys()),
                    **{f"store_{k}": v for k, v in self.store.stats.items()})
        obs.absorb_stats("engine", snap)   # mirror into the shared registry
        return snap

    def _pool(self) -> ThreadPoolExecutor:
        if self._prefetch_pool is None:
            depth = max(1, int(getattr(self.plan, "prefetch_depth", 1)))
            pool = ThreadPoolExecutor(
                max_workers=depth, thread_name_prefix="repro-shard-prefetch")
            self._prefetch_pool = pool
            # a graph dropped without close() must not strand its workers
            self._pool_finalizer = weakref.finalize(
                self, _shutdown_pool, pool)
        return self._prefetch_pool

    def close(self) -> None:
        """Shut down the prefetch pool and settle in-flight fetches.
        Idempotent, and not final: a later :meth:`matmat` lazily recreates
        the pool.  ``run_job`` and the estimator call this at teardown so
        fits never strand ``repro-shard-prefetch`` threads."""
        self._drain_prefetch()
        pool, self._prefetch_pool = self._prefetch_pool, None
        fin, self._pool_finalizer = self._pool_finalizer, None
        if pool is not None:
            pool.shutdown(wait=True)
        if fin is not None:
            fin.detach()
        self.store.join_writer()   # nor a store writer thread

    def _resolve_impl(self) -> str:
        impl = self.matmat_impl
        if impl not in _SCATTER_IMPLS:
            raise ValueError(f"matmat_impl must be one of {_SCATTER_IMPLS}, "
                             f"got {impl!r}")
        if impl == "auto":
            return "device" if jax.default_backend() != "cpu" else "host"
        return impl

    def matmat(self, V: np.ndarray) -> np.ndarray:
        """S @ V streaming one shard at a time — each CSR shard is pulled
        from the (possibly spilled) store ONCE PER BLOCK and its product
        amortized over all b columns, instead of once per vector; under a
        memory budget this divides the spill-reload traffic of an
        eigensolve by the block width.

        Shard gets run ``plan.prefetch_depth`` deep on a worker pool:
        while shard c multiplies, shards c+1..c+depth are already being
        fetched (spill-reload I/O included, in parallel — the store is
        thread-safe), and as the call returns the NEXT call's first
        window of shards starts loading, overlapping the eigensolver's
        QR / reorthogonalization work between passes.  On accelerator backends
        the per-shard product is a jitted device segment-sum, so shard
        c+1's upload overlaps shard c's multiply and the host only joins
        the results once at the end (``matmat_impl`` pins the scatter:
        "device" | "host" | "loop").  A fetch that finished before the
        consumer asked counts as a ``prefetch_hit`` (misses = the
        consumer had to wait); both land in ``stats_snapshot()`` and
        hence ``info_["engine"]``."""
        V = np.asarray(V)
        if V.ndim == 1:
            V = V[:, None]
        b = V.shape[1]
        Y = np.zeros((self.n, b), np.float32)
        self.stats.setdefault("prefetch_hits", 0)
        self.stats.setdefault("prefetch_misses", 0)
        impl = self._resolve_impl()
        pool = self._pool()
        ranges = self.plan.ranges
        nshards = len(ranges)
        depth = max(1, int(getattr(self.plan, "prefetch_depth", 1)))
        warm, self._warm = self._warm, None
        futs: Dict[int, object] = dict(warm or {})
        for c in range(min(depth, nshards)):     # fill the readahead window
            if c not in futs:
                futs[c] = self._submit_fetch(pool, c)
        V_dev = jnp.asarray(V, jnp.float32) if impl == "device" else None
        pending = []                             # (r0, r1, device result)
        for c, (r0, r1) in enumerate(ranges):
            fut = futs.pop(c)
            if c + depth < nshards:              # keep the window full —
                futs[c + depth] = self._submit_fetch(  # submitted BEFORE
                    pool, c + depth)             # joining c, so a stall
            self.stats["prefetch_hits" if fut.done()   # here is fetch time
                       else "prefetch_misses"] += 1
            sh = fut.result()
            indptr, indices, data = sh["indptr"], sh["indices"], sh["data"]
            rows = np.repeat(np.arange(r1 - r0), np.diff(indptr))
            if impl == "device":
                # pow2 nnz buckets bound recompiles; zero padding is inert
                target = max(256, 1 << max(0, int(len(data)) - 1).bit_length())
                out = _csr_segment_matmat(
                    jnp.asarray(_pad_nnz(data.astype(np.float32), target)),
                    jnp.asarray(_pad_nnz(indices, target)),
                    jnp.asarray(_pad_nnz(rows, target)),
                    V_dev, r1 - r0)
                pending.append((r0, r1, out))    # don't block: double-buffer
            elif impl == "host":
                scatter_rows(Y[r0:r1], rows, data[:, None] * V[indices])
            else:                                # "loop": PR-7 reference
                Y[r0:r1] = _bincount_loop_rows(rows,
                                               data[:, None] * V[indices],
                                               r1 - r0)
        for r0, r1, out in pending:              # one host join at the end
            Y[r0:r1] = np.asarray(out)
        # warm the next pass's first WINDOW while the caller (eigensolver)
        # crunches its Rayleigh-Ritz / orthogonalization step — without
        # this, every pass would re-miss its first depth-1 shards
        self._warm = {c: self._submit_fetch(pool, c)
                      for c in range(min(depth, nshards))}
        return Y

    def to_dense(self) -> np.ndarray:
        """Dense S — test/oracle path only; defeats the engine if used at
        scale."""
        self._drain_prefetch()      # serialize vs the background workers
        S = np.zeros((self.n, self.n), np.float32)
        for c, (r0, r1) in enumerate(self.plan.ranges):
            sh = self.shard(c)
            indptr, indices, data = sh["indptr"], sh["indices"], sh["data"]
            rows = np.repeat(np.arange(r0, r1), np.diff(indptr))
            S[rows, indices] = data
        return S


def make_normalized_operator(graph: ShardedCSRGraph, dtype=jnp.float32,
                             mesh=None, pad_to: int | None = None
                             ) -> NormalizedOperator:
    """Wrap the sharded graph as the estimator's common operator interface:
    ``A V = valid*V + D^{-1/2} S D^{-1/2} V`` with the S product streaming
    shards through a host callback.

    Two views of the same product are exposed: the traced ``matmat``
    (``pure_callback`` inside the computation — composable with any jitted
    consumer) and ``host_matmat``, the identical arithmetic as plain numpy
    on the host.  Eigensolvers prefer ``host_matmat`` and drive the
    recurrence step-by-step (``lanczos.block_run_host``): on hosts where
    the CPU runtime's worker pool has a single thread, the callback
    machinery can deadlock against its own operand transfer, so the hot
    path must not run the matrix pass inside a traced computation.

    ``pad_to`` rounds n_pad up (the estimator's mesh-divisibility
    invariant — every other affinity pads to a device-count multiple, and
    downstream shard_map stages require it); padding rows are zero-degree
    and masked out of ``valid`` exactly like the dense backends'.
    """
    n = graph.n
    n_pad = max(n, pad_to or n)
    deg = jnp.zeros((n_pad,), dtype).at[:n].set(jnp.asarray(graph.deg, dtype))
    inv_sqrt = jnp.where(deg > 0, 1.0 / jnp.sqrt(jnp.maximum(deg, 1e-12)), 0.0)
    valid = (jnp.arange(n_pad) < n).astype(dtype)

    def host_matmat(V):
        return graph.matmat(np.asarray(V, np.float32))

    def matmat(V: jax.Array) -> jax.Array:
        b = V.shape[1]
        out_shape = jax.ShapeDtypeStruct((n, b), jnp.float32)
        SV = jax.pure_callback(host_matmat, out_shape,
                               (inv_sqrt[:, None] * V)[:n].astype(jnp.float32))
        SV = jnp.zeros((n_pad, b), dtype).at[:n].set(SV.astype(dtype))
        return valid[:, None] * V + inv_sqrt[:, None] * SV

    # the SAME normalized product, entirely on the host (numpy in/out) —
    # elementwise f32 mul/add matches the traced version bitwise, and the
    # S matmat is the identical graph.matmat either way
    inv_np = np.asarray(inv_sqrt, np.float32)
    valid_np = np.asarray(valid, np.float32)

    def host_normalized_matmat(V: np.ndarray) -> np.ndarray:
        V = np.asarray(V, np.float32)
        SV = graph.matmat(np.ascontiguousarray((inv_np[:, None] * V)[:n]))
        SVp = np.zeros((n_pad, V.shape[1]), np.float32)
        SVp[:n] = SV
        return valid_np[:, None] * V + inv_np[:, None] * SVp

    def dense() -> jax.Array:
        S = jnp.zeros((n_pad, n_pad), dtype).at[:n, :n].set(
            jnp.asarray(graph.to_dense(), dtype))
        return jnp.diag(valid) + S * (inv_sqrt[:, None] * inv_sqrt[None, :])

    return NormalizedOperator(
        matmat=matmat, valid=valid, inv_sqrt=inv_sqrt, n=n, n_pad=n_pad,
        mesh=mesh, schedule=None, dense=dense, stats=graph.stats_snapshot,
        close=graph.close,
        # f32-only: the host arithmetic is written in f32; other compute
        # dtypes fall back to the traced callback matmat
        host_matmat=(host_normalized_matmat
                     if jnp.dtype(dtype) == jnp.float32 else None))
