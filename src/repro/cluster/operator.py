"""The common product of every affinity backend.

All affinity backends — dense, triangular, compact, precomputed, graph,
knn-topt — reduce to the same object: the *shifted normalized operator*

    A v = valid * v + D^{-1/2} S D^{-1/2} v

whose largest eigenpairs are the smallest of L_sym = I - D^{-1/2} S D^{-1/2}
(see ``core.laplacian``).  Eigensolver backends consume only this interface,
so any affinity composes with any eigensolver; the ``schedule`` /
``unpermute`` bookkeeping hides whether rows are block-permuted (triangular
schedules) or in original order (dense paths).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp


@dataclass
class SpectralResult:
    """Result bundle in original point order (also what the legacy
    ``repro.core.spectral`` entry points return)."""
    labels: jax.Array            # (n,) original point order
    embedding: jax.Array         # (n, k) row-normalized eigenvector rows
    eigenvalues: jax.Array       # (k,) smallest of L_sym, ascending
    centers: jax.Array           # (k, k)
    sigma: jax.Array
    info: dict = field(default_factory=dict)


@dataclass
class NormalizedOperator:
    """Shifted normalized-similarity operator plus its padding/permutation
    bookkeeping.

    matmat:    (n_pad, b) -> (n_pad, b) replicated; ``A V`` as above, one
               pass over the similarity per block of any width b, 1
               included.  A ``matmat`` given as a ``jax.tree_util.Partial``
               of a module-level function over arrays (the dense operator
               of ``core.laplacian.make_dense_operator``) is data: the
               Lanczos eigensolvers then reuse one compiled loop across
               fits of the same shapes.  A plain closure is traced again
               on every fit.
    valid:     (n_pad,) 1/0 mask — 0 on padding rows.
    inv_sqrt:  (n_pad,) D^{-1/2} of the (padded) similarity; kept so the
               estimator can Nystrom-extend the embedding to new points.
    n, n_pad:  true vs padded point count; rows may be permuted (schedule).
    mesh:      device mesh the similarity is sharded over.
    schedule:  ``BlockSchedule`` when rows are block-permuted, else None.
    dense:     optional zero-arg callable materializing A (n_pad, n_pad)
               exactly — used by the ``eigh`` backend; falls back to
               applying ``matmat`` to identity blocks when absent.
    stats:     backend-reported build statistics (e.g. the engine's
               map/shuffle/reduce counters); merged into ``est.info_``.
               Either a dict or a zero-arg callable returning one — a
               callable is re-evaluated at read time, so backends whose
               counters keep moving after construction (shard-store
               spills during the eigensolve) report live numbers.
    reset:     optional zero-arg callable restoring the backend's live
               counters to their post-build baseline.  The estimator
               calls :meth:`reset_stats` before each eigensolve so a
               REUSED operator reports per-fit numbers instead of
               accumulating across fits (fresh operators: no-op).
    close:     optional zero-arg callable releasing backend worker
               resources (the engine's shard-prefetch pool).  The
               estimator calls it (when set) as a fit finishes so no
               background threads outlive it; backends must treat it as
               non-final (a reused operator's next matmat restarts
               whatever close released).
    count_passes: optional callable ``(passes, width)`` counting the
               backend's executed passes.  A product traced into a
               compiled loop runs without Python, so the eigensolvers
               report the passes their loops made through
               :meth:`record_passes` (no-op for backends without one).
    start_block: optional ``(Q0, W0)``: an orthonormal (n_pad, b) start
               block and ``A Q0``, computed in the backend's degree pass
               when the estimator asked for one of width b (a backend
               with a ``folds_start`` attribute, an eigensolver with a
               ``start_width`` attribute).  The block-Lanczos solver
               starts from it and makes one pass fewer.
    host_matmat: optional plain-host (numpy (n_pad, b) -> (n_pad, b))
               view of the SAME product, set by streaming backends whose
               matmat wraps host code in ``pure_callback``.  Eigensolvers
               that see it drive the recurrence step-by-step from Python
               (``core.lanczos.block_run_host``) instead of tracing the
               callback into one computation — the callback machinery can
               self-deadlock on single-thread CPU runtimes.
    """

    valid: jax.Array
    inv_sqrt: jax.Array
    n: int
    n_pad: int
    mesh: Any
    matmat: Callable[[jax.Array], jax.Array]
    schedule: Any = None
    dense: Optional[Callable[[], jax.Array]] = None
    stats: Any = field(default_factory=dict)
    reset: Optional[Callable[[], None]] = None
    close: Optional[Callable[[], None]] = None
    count_passes: Optional[Callable[[int, int], None]] = None
    start_block: Optional[tuple] = None
    host_matmat: Optional[Callable] = None

    def stats_snapshot(self) -> dict:
        return dict(self.stats() if callable(self.stats) else self.stats)

    def reset_stats(self) -> None:
        """Restore live backend counters to their post-build baseline
        (no-op for backends without one)."""
        if self.reset is not None:
            self.reset()

    def record_passes(self, passes: int, width: int) -> None:
        """Count ``passes`` products of ``width`` columns that a compiled
        loop made (no-op for backends without a counter)."""
        if self.count_passes is not None:
            self.count_passes(passes, width)

    def unpermute(self, values: jax.Array) -> jax.Array:
        """Per-(padded-)row values -> original point order, padding dropped."""
        if self.schedule is not None:
            return values[jnp.asarray(self.schedule.inv_perm)][: self.n]
        return values[: self.n]

    def materialize(self, block: int = 128) -> jax.Array:
        """Dense A — exact if the backend provided ``dense``, else assembled
        through ``matmat`` applied to identity column blocks (small-n
        fallback).  Blocks keep the working set bounded for streaming
        backends while still amortizing each matrix pass over ``block``
        columns."""
        if self.dense is not None:
            return self.dense()
        eye = jnp.eye(self.n_pad, dtype=self.valid.dtype)
        cols = [self.matmat(eye[:, c0: c0 + block])
                for c0 in range(0, self.n_pad, block)]
        return jnp.concatenate(cols, axis=1)
