"""Batched cluster-assignment service over a persisted spectral model.

The clustering analogue of ``launch/serve.py``'s continuous batching: a
fitted :class:`~repro.cluster.SpectralClustering` model is loaded from
disk (``est.save`` / ``SpectralClustering.load``) and served against a
queue of predict requests, each carrying a variable number of query
points.  XLA shapes are static, so every service step runs ONE fixed
``(B, d)`` predict batch: pending request rows are packed into the batch
buffer (a request larger than B streams through over several steps), a
liveness mask marks the filled rows, and the compiled fused Nystrom
transform embeds + assigns the whole batch in one pass over the training
set — unfilled rows ride along as padding and are discarded on scatter.

    PYTHONPATH=src python -m repro.launch.cluster_serve \\
        --fit-blobs 512 --k 3 --model-dir /tmp/spectral-model \\
        --requests 8 --points-per-request 100

With an existing ``--model-dir`` the fit step is skipped: the service
loads and serves (fit once, serve anywhere — including a different device
count, the checkpoint is elastic).

Admission control (the resilience contract, see API.md "Fault
tolerance"): the server optionally bounds its pending-row backlog
(``max_pending_rows``) — a submit that would blow the bound is *shed*
with a typed :class:`~repro.cluster.serving.QueueFullError` instead of
growing the queue without limit — and every request may carry a deadline
(``deadline_s``, or the server-wide ``default_deadline_s``): requests
that sit past it are *expired* with a typed
:class:`~repro.cluster.serving.DeadlineExceededError` and their
remaining rows never occupy batch slots.  ``serve.queue_depth`` (gauge),
``serve.shed`` and ``serve.expired`` (counters) track it in the obs
registry.
"""
from __future__ import annotations

import argparse
import copy
import time
from collections import deque
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache, obs
from repro.cluster.serving import DeadlineExceededError, QueueFullError

# the fitted arrays est.predict reads
_MODEL_STATE = ("_train_x", "_eigvecs", "_inv_sqrt", "eigenvalues_",
                "sigma_", "centers_")


@dataclass
class PredictRequest:
    rid: int
    points: np.ndarray                       # (m_i, d) float32
    labels: np.ndarray | None = None         # filled on completion
    t_submit: float = 0.0
    t_done: float = 0.0
    _filled: int = field(default=0, repr=False)   # rows already served
    deadline_s: float | None = None          # per-request; None = server's
    status: str = "pending"                  # pending|active|ok|shed|expired
    error: str | None = None                 # typed-rejection message

    @property
    def latency_s(self) -> float:
        return self.t_done - self.t_submit

    @property
    def done(self) -> bool:
        return self.labels is not None and self._filled >= len(self.points)


class ClusterServer:
    """Static-shape batched predict: one (B, d) buffer, liveness mask.

    ``max_pending_rows`` bounds the admission queue (None = unbounded,
    the classic behaviour); ``default_deadline_s`` applies to requests
    that carry no ``deadline_s`` of their own (None = no deadline)."""

    def __init__(self, est, batch_rows: int = 256,
                 max_pending_rows: int | None = None,
                 default_deadline_s: float | None = None):
        est._check_fitted()
        if est._train_x is None:
            raise ValueError("serving needs a feature-space model "
                             "(precomputed-affinity fits cannot predict)")
        if max_pending_rows is not None and max_pending_rows <= 0:
            raise ValueError(f"max_pending_rows must be positive or None, "
                             f"got {max_pending_rows}")
        self.est = est
        self.B = int(batch_rows)
        self.d = int(est._train_x.shape[1])
        self.max_pending_rows = max_pending_rows
        self.default_deadline_s = default_deadline_s
        self.steps = 0
        self.stats = {"batches": 0, "rows_live": 0, "rows_padded": 0,
                      "shed": 0, "expired": 0}
        # the SHARED histogram type backs both the live metrics and
        # summarize()'s p50/p95/p99 (exact nearest-rank at service scale)
        self.batch_ms = obs.histogram("serve.batch_ms")
        self.request_ms = obs.histogram("serve.request_ms")
        # one compiled predict for the one static shape the service runs;
        # est.predict routes (dense/fused) on static metadata, so the
        # whole embed+assign pipeline traces into a single computation.
        # The model's arrays are arguments, not constants folded into the
        # program: it stays small, compiles without constant folding over
        # the training set, and its compile-cache key depends on shapes
        # only, so any model of the same shape loads it
        self._model = tuple(getattr(est, name) for name in _MODEL_STATE)

        def predict(xb, model):
            view = copy.copy(est)      # shares info_ and the kernel cache
            for name, value in zip(_MODEL_STATE, model):
                setattr(view, name, value)
            return view.predict(xb)

        jitted = jax.jit(predict)
        self._predict = lambda xb: jitted(xb, self._model)

    # -- admission control ---------------------------------------------------

    @staticmethod
    def pending_rows(active: deque) -> int:
        """Rows admitted but not yet served (the backlog the admission
        bound and the queue-depth gauge measure)."""
        return sum(len(r.points) - r._filled for r in active)

    def admit(self, req: PredictRequest, active: deque,
              now: float | None = None) -> bool:
        """Admit ``req`` into the active window, or shed it with a typed
        rejection when the pending-row backlog is at its bound.  A
        request larger than the whole bound is still admitted when the
        queue is empty (it would otherwise be undeliverable) — it streams
        through B rows per step like any oversized request."""
        now = time.perf_counter() if now is None else now
        if req.t_submit == 0.0:
            req.t_submit = now
        rows = len(req.points)
        if self.max_pending_rows is not None:
            pending = self.pending_rows(active)
            if pending > 0 and pending + rows > self.max_pending_rows:
                err = QueueFullError(req.rid, rows, pending,
                                     self.max_pending_rows)
                req.status, req.error, req.t_done = err.status, str(err), now
                self.stats["shed"] += 1
                obs.counter("serve.shed").inc()
                return False
        req.status = "active"
        active.append(req)
        obs.gauge("serve.queue_depth").set(self.pending_rows(active))
        return True

    def _expire(self, active: deque, now: float) -> int:
        """Drop admitted requests that sat past their deadline; their
        remaining rows never occupy batch slots."""
        expired = 0
        for req in list(active):
            ddl = (req.deadline_s if req.deadline_s is not None
                   else self.default_deadline_s)
            if ddl is None or req.done:
                continue
            waited = now - req.t_submit
            if waited > ddl:
                err = DeadlineExceededError(req.rid, ddl, waited)
                req.status, req.error, req.t_done = err.status, str(err), now
                active.remove(req)
                expired += 1
        if expired:
            self.stats["expired"] += expired
            obs.counter("serve.expired").inc(expired)
        return expired

    def _pack(self, active: deque) -> tuple[np.ndarray, np.ndarray, list]:
        """Fill the (B, d) buffer from the active queue (FIFO, splitting
        requests that don't fit); returns (buffer, liveness mask,
        [(request, row_start_in_request, rows, batch_row0), ...])."""
        buf = np.zeros((self.B, self.d), np.float32)
        mask = np.zeros((self.B,), bool)
        placed = []
        row = 0
        for req in active:
            if row == self.B:
                break
            take = min(self.B - row, len(req.points) - req._filled)
            if take <= 0:
                continue
            buf[row: row + take] = req.points[req._filled: req._filled + take]
            mask[row: row + take] = True
            placed.append((req, req._filled, take, row))
            row += take
        return buf, mask, placed

    def step(self, active: deque) -> int:
        """One static-shape predict over the packed batch; scatters labels
        back and retires completed requests (expiring any that outlived
        their deadline first).  Returns rows served."""
        self._expire(active, time.perf_counter())
        buf, mask, placed = self._pack(active)
        if not placed:
            return 0
        with obs.span("serve.step", batch_rows=self.B) as sp:
            t0 = time.perf_counter()
            labels = np.asarray(self._predict(jnp.asarray(buf)))
            now = time.perf_counter()
            self.batch_ms.observe(1e3 * (now - t0))
            for req, start, take, row0 in placed:
                if req.labels is None:
                    req.labels = np.empty(len(req.points), labels.dtype)
                req.labels[start: start + take] = labels[row0: row0 + take]
                req._filled += take
                if req.done:
                    req.t_done = now
                    req.status = "ok"
                    self.request_ms.observe(1e3 * req.latency_s)
            while active and active[0].done:
                active.popleft()
            obs.gauge("serve.queue_depth").set(self.pending_rows(active))
            live = int(mask.sum())
            sp.set(rows_live=live)
        self.steps += 1
        self.stats["batches"] += 1
        self.stats["rows_live"] += live
        self.stats["rows_padded"] += self.B - live
        obs.counter("serve.batches").inc()
        obs.counter("serve.rows_live").inc(live)
        obs.counter("serve.rows_padded").inc(self.B - live)
        obs.gauge("serve.fill").set(
            self.stats["rows_live"]
            / max(self.stats["rows_live"] + self.stats["rows_padded"], 1))
        return live

    def run(self, queue: list[PredictRequest]) -> list[PredictRequest]:
        """Serve every request that survives admission to completion
        (requests enter the active window in arrival order; the window
        drains front-first, so a big request streams through B rows per
        step without starving the batch — trailing slack is refilled from
        the queue).  Shed and expired requests come back with their typed
        status/``error`` set instead of labels."""
        t0 = time.perf_counter()
        active: deque = deque()
        for req in queue:
            req.t_submit = t0
            if len(req.points) == 0:             # degenerate: nothing to do
                req.labels = np.empty((0,), np.int32)
                req.t_done = t0
                req.status = "ok"
                continue
            self.admit(req, active, now=t0)
        while active:
            self.step(active)
        return list(queue)


def summarize(done: list[PredictRequest], wall_s: float) -> dict:
    # the shared histogram type does the percentile math: exact
    # nearest-rank (p50 of [a, b] is a; p99 of n=1 is that sample —
    # no len//2 off-by-one on small n).  Latency percentiles cover
    # COMPLETED requests only; shed/expired are counted separately.
    ok = [r for r in done if r.done]
    hist = obs.Histogram("serve.summary_latency_ms")
    for r in ok:
        hist.observe(1e3 * r.latency_s)
    total = sum(len(r.points) for r in ok)
    return {
        "requests": len(done),
        "completed": len(ok),
        "shed": sum(r.status == "shed" for r in done),
        "expired": sum(r.status == "expired" for r in done),
        "points": total,
        "points_per_s": total / max(wall_s, 1e-9),
        "latency_p50_ms": hist.percentile(50),
        "latency_p95_ms": hist.percentile(95),
        "latency_p99_ms": hist.percentile(99),
        "latency_max_ms": 1e3 * max((r.latency_s for r in ok),
                                    default=0.0),
    }


def main(argv=None):
    from repro.cluster import SpectralClustering
    from repro.data import synthetic

    ap = argparse.ArgumentParser()
    ap.add_argument("--model-dir", required=True,
                    help="persisted model (est.save); with --fit-blobs the "
                         "model is fitted and saved here first")
    ap.add_argument("--fit-blobs", type=int, default=0,
                    help="fit a fresh model on n blob points, save it to "
                         "--model-dir, then reload it (fit -> save -> load "
                         "-> serve round trip)")
    ap.add_argument("--k", type=int, default=3)
    ap.add_argument("--affinity", default="fused-rbf")
    ap.add_argument("--eigensolver", default="block-lanczos")
    ap.add_argument("--lanczos-steps", type=int, default=64)
    ap.add_argument("--transform-path", default="auto",
                    choices=["auto", "dense", "fused"])
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--points-per-request", type=int, default=100)
    ap.add_argument("--batch-rows", type=int, default=256)
    ap.add_argument("--max-pending-rows", type=int, default=None,
                    help="bounded admission queue: shed requests that "
                         "would push the pending backlog past this many "
                         "rows (default: unbounded)")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="server-wide request deadline; requests that sit "
                         "past it are expired with a typed rejection")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace-out", default=None, metavar="FILE.json",
                    help="write a Chrome-trace of the run (chrome://tracing)")
    ap.add_argument("--metrics-out", default=None, metavar="FILE.json",
                    help="write the metrics registry snapshot as JSON")
    args = ap.parse_args(argv)
    compile_cache.enable()

    if args.fit_blobs:
        pts, _ = synthetic.blobs(args.fit_blobs, args.k, dim=8, spread=0.6,
                                 seed=args.seed)
        est = SpectralClustering(
            k=args.k, affinity=args.affinity, eigensolver=args.eigensolver,
            sigma=1.0, lanczos_steps=args.lanczos_steps,
            transform_path=args.transform_path, seed=args.seed)
        t0 = time.perf_counter()
        est.fit(jnp.asarray(pts))
        print(f"[cluster_serve] fit n={args.fit_blobs} "
              f"affinity={args.affinity} in {time.perf_counter() - t0:.1f}s")
        if "obs" in est.info_:
            print(obs.phase_summary(est.info_["obs"]))
        est.save(args.model_dir)
        print(f"[cluster_serve] saved -> {args.model_dir}")

    est = SpectralClustering.load(args.model_dir)
    est.transform_path = args.transform_path
    n, d = est._train_x.shape
    print(f"[cluster_serve] loaded model: n={n} d={d} k={est.k} "
          f"devices={len(jax.devices())}")

    rng = np.random.RandomState(args.seed + 1)
    train = np.asarray(est._train_x)
    queue = []
    for rid in range(args.requests):
        m = max(1, args.points_per_request + rng.randint(-20, 21))
        idx = rng.choice(n, size=m)
        queue.append(PredictRequest(
            rid=rid, points=(train[idx]
                             + 0.05 * rng.randn(m, d)).astype(np.float32)))

    srv = ClusterServer(est, batch_rows=args.batch_rows,
                        max_pending_rows=args.max_pending_rows,
                        default_deadline_s=args.deadline_s)
    t0 = time.perf_counter()
    done = srv.run(queue)
    wall = time.perf_counter() - t0
    s = summarize(done, wall)
    fill = srv.stats["rows_live"] / max(
        srv.stats["rows_live"] + srv.stats["rows_padded"], 1)
    path = est.info_.get("transform", {}).get("path", "n/a")
    print(f"[cluster_serve] {s['requests']} requests "
          f"({s['completed']} ok, {s['shed']} shed, {s['expired']} "
          f"expired), {s['points']} points, "
          f"{srv.steps} batch steps ({fill:.0%} fill), {wall:.2f}s "
          f"({s['points_per_s']:.0f} pts/s, "
          f"p50={s['latency_p50_ms']:.0f}ms p95={s['latency_p95_ms']:.0f}ms "
          f"p99={s['latency_p99_ms']:.0f}ms max={s['latency_max_ms']:.0f}ms) "
          f"path={path}")
    print(f"[obs] serve wall={wall:.3f}s batches={srv.stats['batches']} "
          f"fill={fill:.0%} request_p99_ms={s['latency_p99_ms']:.1f} "
          f"shed={s['shed']} expired={s['expired']}")
    obs.write_artifacts(args.trace_out, args.metrics_out)
    assert all(r.done for r in done
               if r.status not in ("shed", "expired"))
    assert all(len(r.labels) == len(r.points) for r in done if r.done)


if __name__ == "__main__":
    main()
