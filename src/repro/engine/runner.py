"""The job runner: executes a :class:`JobPlan` as map/shuffle/reduce
tasks and drives the eigensolve + streaming k-means off the resulting
shards — ``engine.run_job(plan, reader)`` is the out-of-core analogue of
``SpectralClustering.fit``.

The build is a **dependency-driven scheduler** over a worker pool of
``plan.workers`` threads (the Hadoop fan-out, one host): each chunk's
shuffle is submitted the moment its last input tile lands — no per-stage
barrier — and the reduces fan out the instant the final shuffle finishes
(a reduce folds mirror blocks that ANY shuffle may emit, the same
all-map-outputs dependency Hadoop's reduce fetch has).  All state between
tasks lives in the thread-safe ShardStore, so the working set is bounded
by the memory budget regardless of n; tasks never share mutable state
beyond it, and each task's arithmetic is order-independent, so results
are bitwise-identical at every pool width (``workers=1`` reproduces the
classic sequential schedule exactly).
"""
from __future__ import annotations

import queue
import statistics
import threading
import time
from concurrent.futures import FIRST_COMPLETED, Future, wait
from dataclasses import dataclass, field
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import kmeans as km, lanczos as lz, similarity as sim
from repro.engine import kmeans as skm, tasks
from repro.engine.operator import (ShardedCSRGraph, make_normalized_operator)
from repro.engine.plan import JobPlan, route_path
from repro.engine.store import ShardStore


class EngineError(RuntimeError):
    """Base class for engine scheduling failures."""


class EngineTimeoutError(EngineError):
    """A build stage blew its ``plan.stage_timeout_s`` deadline.  Raised
    by the scheduler after cancelling every queued task; running attempts
    are NOT joined (threads cannot be killed) — they are abandoned on
    their daemon worker threads (see :class:`_DaemonPool`), so the
    deadline genuinely bounds the caller's wall time even when an attempt
    hangs in blocked I/O or an infinite loop.  An abandoned attempt may
    still write into the failed job's store before its thread exits; the
    store is job-private and discarded with the job, so nothing observes
    those writes."""

    def __init__(self, stage: str, seconds: float):
        super().__init__(f"engine stage {stage!r} exceeded its "
                         f"{seconds:g}s deadline")
        self.stage = stage
        self.seconds = seconds


class _DaemonPool:
    """Minimal executor over DAEMON worker threads: ``submit`` returns a
    real :class:`concurrent.futures.Future` (so ``wait`` interoperates),
    ``shutdown`` matches the stdlib signature.

    Exists because ``ThreadPoolExecutor`` joins its non-daemon workers at
    shutdown *and* interpreter exit: one attempt stuck in blocked I/O
    would hang the job (and the process) forever, which is exactly what
    ``plan.stage_timeout_s`` promises cannot happen.  Daemon workers let
    the deadline path call ``shutdown(wait=False)`` and abandon a hung
    attempt — the zombie thread can finish in the background or die with
    the interpreter, but it can no longer block anyone.  Every other
    failure path keeps ``wait=True`` and loses nothing."""

    def __init__(self, max_workers: int, thread_name_prefix: str = "pool"):
        self._q: "queue.SimpleQueue" = queue.SimpleQueue()
        self._lock = threading.Lock()
        self._pending: set = set()          # submitted, not yet picked up
        self._shutdown = False
        self._threads = []
        for i in range(max(1, int(max_workers))):
            t = threading.Thread(target=self._worker, daemon=True,
                                 name=f"{thread_name_prefix}_{i}")
            t.start()
            self._threads.append(t)

    def _worker(self) -> None:
        while True:
            item = self._q.get()
            if item is None:                # shutdown sentinel
                return
            fut, fn = item
            with self._lock:
                self._pending.discard(fut)
            if not fut.set_running_or_notify_cancel():
                continue                    # cancelled while queued
            try:
                fut.set_result(fn())
            except BaseException as e:      # noqa: BLE001 — future carries it
                fut.set_exception(e)

    def submit(self, fn: Callable) -> Future:
        fut: Future = Future()
        with self._lock:
            if self._shutdown:
                raise RuntimeError("cannot submit to a shut-down pool")
            self._pending.add(fut)
        self._q.put((fut, fn))
        return fut

    def shutdown(self, wait: bool = True,
                 cancel_futures: bool = False) -> None:
        with self._lock:
            first = not self._shutdown
            self._shutdown = True
            doomed = list(self._pending) if cancel_futures else []
        for fut in doomed:
            fut.cancel()                    # running ones decline, as stdlib
        if first:
            for _ in self._threads:
                self._q.put(None)
        if wait:
            for t in self._threads:
                t.join()


@dataclass
class JobResult:
    labels: np.ndarray           # (n,) int32
    embedding: np.ndarray        # (n, k) row-normalized
    eigenvalues: np.ndarray      # (k,) smallest of L_sym, ascending
    centers: np.ndarray          # (k, k)
    sigma: float
    graph: Optional[ShardedCSRGraph]   # None on the fused (matrix-free) path
    stats: Dict = field(default_factory=dict)


def _resolve_sigma(reader, plan: JobPlan, sample_rows: int = 1024) -> float:
    """Median-distance heuristic on a sample STRIDED across all chunks.

    Sampling only the leading chunks (the pre-PR8 behaviour) skews sigma
    whenever the chunk order is meaningful — class-sorted data would
    estimate the bandwidth of one cluster instead of the dataset — so up
    to 8 evenly-spaced chunks each contribute an equal share of the
    sample."""
    if plan.sigma is not None:
        return float(plan.sigma)
    nc = plan.nchunks
    idx = np.unique(np.linspace(0, nc - 1, min(nc, 8)).round().astype(int))
    per = -(-sample_rows // len(idx))            # equal share per chunk
    xs = np.concatenate([np.asarray(reader[int(c)])[:per]
                         for c in idx])[:sample_rows]
    return float(sim.median_sigma(jnp.asarray(xs)))


@dataclass
class _TaskState:
    """Scheduler-side bookkeeping for one logical task across attempts."""
    kind: str
    key: object
    attempts: int = 0            # attempts launched so far
    failures: int = 0
    inflight: int = 0            # attempts currently submitted/running
    done: bool = False           # first successful completion landed
    backup: bool = False         # a speculative duplicate was launched


def _schedule_build(reader, sigma, plan: JobPlan, store: ShardStore,
                    overlap_work: Optional[Callable[[], None]] = None
                    ) -> tuple[np.ndarray, int, Dict]:
    """Run every map/shuffle/reduce task on a ``plan.workers``-wide pool,
    releasing each task the moment its inputs exist:

      map (i, j)   no deps — all submitted up front
      shuffle c    the map tiles touching chunk c (row i == c or j == c)
      reduce c     ALL shuffles (any shuffle may mirror triplets into c)

    Fault tolerance (the Hadoop task-attempt model):

      * a failed attempt is resubmitted with exponential backoff up to
        ``plan.max_retries`` times; tasks are deterministic functions of
        the store, so a retried success is bitwise-identical.  In consume
        mode a failed shuffle/reduce attempt may have already deleted
        part of its input set (it consumes blocks as it folds), so the
        retry first re-materializes every missing input via the lineage
        path (``tasks.recompute_entry`` — a bitwise replay): a mid-fold
        failure can never make the retry fold a partial input set and
        silently drop neighbours;
      * with ``plan.speculation_factor`` k > 0, a running task whose wall
        exceeds k x the running median of completed walls for its stage
        gets ONE speculative backup attempt — first completion wins, the
        loser's (identical) output is discarded.  In speculation mode
        tasks run ``consume=False`` and the scheduler deletes a task's
        inputs only after every attempt has settled, so a duplicate can
        never read half-deleted inputs;
      * ``plan.stage_timeout_s`` bounds each stage's wall; on expiry
        every queued task is cancelled, running attempts are ABANDONED on
        their daemon workers (joining could hang forever on a stuck
        attempt — see :class:`_DaemonPool`), and the typed error
        propagates, so the deadline bounds the job's wall time.  On retry
        exhaustion the scheduler cancels the queue but does join running
        attempts — a failed-but-not-hung job never leaks tasks that keep
        spilling into the store.

    ``overlap_work`` (if given) runs ONCE on the scheduler thread as soon
    as the last shuffle finishes — i.e. while the reduce tail is still
    draining on the workers — so callers can overlap eigensolver seeding
    with the end of the build.  Returns (deg, nnz, stats)."""
    tiles = plan.tiles
    nc = plan.nchunks
    workers = max(1, int(plan.workers))
    faults = plan.faults
    speculate = plan.speculation_factor > 0
    consume = not speculate
    busy = {"map": 0.0, "shuffle": 0.0, "reduce": 0.0}
    walls = {"map": [], "shuffle": [], "reduce": []}
    busy_lock = threading.Lock()
    deg = np.zeros(plan.n, np.float32)
    nnz_total = 0
    counters = {"retries": 0, "task_failures": 0, "inputs_healed": 0,
                "speculative_launched": 0, "speculative_won": 0}

    def timed(stage, fn, *args, **kw):
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        dt = time.perf_counter() - t0
        with busy_lock:
            busy[stage] += dt
            walls[stage].append(dt)
        return out

    def run_task(kind, key):
        if kind == "map":
            return timed("map", tasks.run_map_task,
                         reader, sigma, plan, key[0], key[1], store)
        if kind == "shuffle":
            return timed("shuffle", tasks.run_shuffle_task,
                         plan, key, store, consume=consume)
        return timed("reduce", tasks.run_reduce_task,
                     plan, key, store, consume=consume)

    tstate: Dict[tuple, _TaskState] = {}
    starts: Dict[tuple, float] = {}       # (kind, key, attempt) -> exec start
    stage_t0: Dict[str, float] = {}
    stage_left = {"map": len(tiles), "shuffle": nc, "reduce": nc}
    waiting = {c: {tl for tl in tiles if c in tl} for c in range(nc)}
    mirror_srcs: Dict[int, set] = {}      # reduce c <- shuffles that fed it
    shuffles_left = nc
    overlap_pending = overlap_work is not None
    t_start = time.perf_counter()
    # speculation / deadlines need a clock tick even when nothing finishes
    poll = 0.05 if (speculate or plan.stage_timeout_s is not None) else None
    pool = _DaemonPool(workers, thread_name_prefix="repro-engine-task")
    futures: Dict = {}

    def heal_inputs(kind, key):
        """Consume-mode retries only: a failed shuffle/reduce attempt
        deletes inputs as it folds, so the retry would otherwise see —
        and silently fold — only the not-yet-consumed remainder.
        Re-materialize every missing input from lineage (a bitwise replay
        of its producing task) before re-running the fold; ``store.keys``
        then presents the full set in the original sorted order, so the
        retried fold is bitwise-identical to an untouched first run."""
        if kind == "shuffle":
            expected = [f"cand/{key}/{min(key, o)}-{max(key, o)}"
                        for o in range(nc)]
        elif kind == "reduce":
            expected = ([f"topt/{key}"] +
                        [f"mirror/{key}/{s}"
                         for s in sorted(mirror_srcs.get(key, ()))])
        else:
            return                        # map tasks consume nothing
        for skey in expected:
            if skey in store:
                continue
            store.put(skey, tasks.recompute_entry(reader, sigma, plan, skey))
            with busy_lock:
                counters["inputs_healed"] += 1
            obs.counter("engine.inputs_healed").inc()

    def submit(kind, key, attempt=0, speculative=False):
        st = tstate.setdefault((kind, key), _TaskState(kind, key))
        st.attempts += 1
        st.inflight += 1
        stage_t0.setdefault(kind, time.perf_counter())

        def body(kind=kind, key=key, attempt=attempt,
                 speculative=speculative):
            if attempt > 0 and not speculative and plan.retry_backoff_s:
                time.sleep(min(plan.retry_backoff_s * 2 ** (attempt - 1),
                               2.0))
            if attempt > 0 and consume:
                heal_inputs(kind, key)
            starts[(kind, key, attempt)] = time.perf_counter()
            if faults is not None:
                faults.on_task_start(kind, key, attempt)
            return run_task(kind, key)

        futures[pool.submit(body)] = (kind, key, attempt, speculative)

    def finish(kind, key, out):
        nonlocal shuffles_left, nnz_total
        if kind == "map":
            for c in set(key):
                deps = waiting[c]
                deps.discard(key)
                if not deps:                 # last tile for chunk c
                    submit("shuffle", c)
        elif kind == "shuffle":
            for d in out:                    # record reduce d's input set
                mirror_srcs.setdefault(d, set()).add(key)
            shuffles_left -= 1
            if shuffles_left == 0:           # mirrors all emitted
                for c in range(nc):
                    submit("reduce", c)
        else:                                # reduce: disjoint slices
            r0, r1 = plan.ranges[key]
            deg[r0:r1] = out["deg"]
            nnz_total += out["nnz"]
        stage_left[kind] -= 1

    def settle(st: _TaskState):
        # speculation mode defers a winning task's input deletes until no
        # attempt (winner or loser) can still be reading them
        if consume or not st.done or st.inflight > 0:
            return
        if st.kind == "shuffle":
            doomed = list(store.keys(f"cand/{st.key}/"))
        elif st.kind == "reduce":
            doomed = [f"topt/{st.key}"] + list(store.keys(f"mirror/{st.key}/"))
        else:
            return
        for k in doomed:
            store.delete(k)

    fatal = None
    timed_out = False
    try:
        for (i, j) in tiles:
            submit("map", (i, j))
        while futures and fatal is None:
            if overlap_pending and shuffles_left == 0:
                overlap_pending = False      # reduce tail is draining
                overlap_work()
            done, _ = wait(list(futures), return_when=FIRST_COMPLETED,
                           timeout=poll)
            now = time.perf_counter()
            for fut in done:
                kind, key, attempt, speculative = futures.pop(fut)
                st = tstate[(kind, key)]
                st.inflight -= 1
                starts.pop((kind, key, attempt), None)
                err = fut.exception()
                if err is None:
                    if not st.done:          # first completion wins
                        st.done = True
                        if speculative:
                            counters["speculative_won"] += 1
                        finish(kind, key, fut.result())
                    # else: the losing duplicate — identical output,
                    # already superseded; discard
                elif not st.done:
                    st.failures += 1
                    counters["task_failures"] += 1
                    if st.failures <= plan.max_retries:
                        counters["retries"] += 1
                        submit(kind, key, attempt=st.attempts)
                    else:
                        fatal = err          # budget exhausted: abort job
                # a losing attempt's error is moot — the task completed
                settle(st)
            if fatal is not None:
                break
            if plan.stage_timeout_s is not None:
                for stage, left in stage_left.items():
                    t0s = stage_t0.get(stage)
                    if (t0s is not None and left > 0
                            and now - t0s > plan.stage_timeout_s):
                        timed_out = True
                        raise EngineTimeoutError(stage, plan.stage_timeout_s)
            if speculate:
                with busy_lock:
                    meds = {s: statistics.median(w) if len(w) >= 3 else None
                            for s, w in walls.items()}
                for kind, key, attempt, spec in list(futures.values()):
                    st = tstate[(kind, key)]
                    med = meds[kind]
                    if st.done or st.backup or spec or med is None:
                        continue
                    t0a = starts.get((kind, key, attempt))
                    if t0a is None:          # queued, not yet running
                        continue
                    if now - t0a > plan.speculation_factor * max(med, 1e-3):
                        st.backup = True     # one backup per task
                        counters["speculative_launched"] += 1
                        submit(kind, key, attempt=st.attempts,
                               speculative=True)
        if fatal is not None:
            raise fatal
    finally:
        # the first unrecoverable failure cancels every queued task and
        # joins the running ones — a failed job never leaks attempts that
        # keep spilling into the store.  A blown stage deadline must NOT
        # join (a hung attempt would hang the join too, defeating the
        # deadline): its running attempts are abandoned on their daemon
        # workers instead, and the job's private store is discarded with
        # the job, so their late writes are unobservable.
        pool.shutdown(wait=not timed_out, cancel_futures=True)
    if not consume:
        # deferred-GC stragglers: losing attempts that re-put an input
        # after its consumer settled (all attempts have joined by now)
        for prefix in ("cand/", "topt/", "mirror/"):
            for k in list(store.keys(prefix)):
                store.delete(k)
    if overlap_pending:                      # degenerate tiny jobs
        overlap_work()
    wall = time.perf_counter() - t_start
    busy_s = sum(busy.values())
    stats = {
        "map_tasks": len(tiles), "shuffle_tasks": nc, "reduce_tasks": nc,
        "chunks": nc, "chunk_size": plan.chunk_size, "t": plan.t_eff,
        "workers": workers, "prefetch_depth": plan.prefetch_depth,
        "max_retries": plan.max_retries,
        "retries": counters["retries"],
        "task_failures": counters["task_failures"],
        "inputs_healed": counters["inputs_healed"],
        "speculative_launched": counters["speculative_launched"],
        "speculative_won": counters["speculative_won"],
        # per-stage numbers are BUSY task-seconds (the stages interleave,
        # so they no longer tile a wall-clock interval); overlap_s is the
        # task-seconds the pool hid inside the build wall
        "map_s": round(busy["map"], 4),
        "shuffle_s": round(busy["shuffle"], 4),
        "reduce_s": round(busy["reduce"], 4),
        "build_wall_s": round(wall, 4),
        "overlap_s": round(max(0.0, busy_s - wall), 4),
    }
    return deg, nnz_total, stats


def _install_lineage_recovery(store: ShardStore, reader, sigma,
                              plan: JobPlan) -> None:
    """Arm the store's recovery hook with the planner's task lineage: a
    corrupt or lost spill entry is rebuilt by re-running the math of its
    producing task (``tasks.recompute_entry`` — bitwise-identical to the
    original), so a ``get`` mid-eigensolve heals instead of crashing.
    Installed BEFORE the build so corruption of any intermediate —
    candidate block, top-t, mirror, CSR shard — recovers too."""
    def recover(key: str, exc: Exception) -> bool:
        try:
            arrays = tasks.recompute_entry(reader, sigma, plan, key)
        except KeyError:
            return False                     # no lineage for this key
        store.put(key, arrays)
        obs.counter("engine.shard_recovered").inc()
        return True

    store.recovery = recover


def build_graph(reader, plan: JobPlan,
                store: Optional[ShardStore] = None,
                overlap_work: Optional[Callable[[], None]] = None,
                prewarm: bool = True) -> tuple[ShardedCSRGraph, float]:
    """Run the map + shuffle + reduce stages on the dependency-driven
    scheduler; returns the sharded graph (with per-stage stats attached)
    and the resolved sigma.  See :func:`_schedule_build` for the task
    dependency structure and the ``overlap_work`` hook.

    ``prewarm`` starts the first shard-window fetches before returning,
    so the consumer's first pass starts hot (off for A/B baselines)."""
    store = store or ShardStore(memory_budget=plan.memory_budget,
                                spill_dir=plan.spill_dir,
                                async_spill=plan.async_spill)
    if plan.faults is not None:
        store.faults = plan.faults
    sigma = _resolve_sigma(reader, plan)
    _install_lineage_recovery(store, reader, sigma, plan)
    with obs.span("engine.build", path="ooc", workers=plan.workers,
                  tasks=len(plan.tiles) + 2 * plan.nchunks):
        deg, nnz, stats = _schedule_build(reader, sigma, plan, store,
                                          overlap_work=overlap_work)
    for key in ("map_tasks", "shuffle_tasks", "reduce_tasks"):
        obs.counter(f"engine.{key}").inc(stats[key])
    graph = ShardedCSRGraph(store=store, plan=plan, deg=deg, nnz=nnz,
                            stats=stats)
    if prewarm:
        graph.prewarm()
    return graph, sigma


def _run_fused(plan: JobPlan, reader) -> JobResult:
    """The planner's fused route: the points fit in memory even though the
    dense similarity would not, so instead of spilling CSR shards the job
    runs the matrix-free fused-RBF operator (O(n*d) affinity memory) with
    the same block eigensolve + streaming k-means tail as the ooc path."""
    from repro.cluster.affinity import build_fused_rbf_operator
    from repro.distrib import mesh_utils

    sigma = _resolve_sigma(reader, plan)
    x = np.concatenate([np.asarray(reader[c], np.float32)
                        for c in range(plan.nchunks)])
    mesh = mesh_utils.local_mesh("rows")
    with obs.span("engine.build", path="fused") as sp_build:
        op = build_fused_rbf_operator(jnp.asarray(x), sigma, mesh,
                                      compute_dtype=plan.compute_dtype)

    key = jax.random.PRNGKey(plan.seed)
    _, k_lan, _k_km = jax.random.split(key, 3)
    b = plan.eff_block_size()
    block_steps = plan.num_block_steps()
    with obs.span("engine.eigensolve", path="fused",
                  block_steps=block_steps) as sp_eig:
        state = lz.block_lanczos(op.matmat, op.n_pad, block_steps, k_lan,
                                 block_size=b)
        op.record_passes(block_steps, b)
        evals, Z = lz.block_topk_of_shifted(state, plan.k)
        jax.block_until_ready(Z)

    Y = np.asarray(km.normalize_rows(Z) * op.valid[:, None])[:plan.n]
    ranges = plan.ranges
    with obs.span("engine.kmeans", path="fused") as sp_km:
        labels, centers = skm.streaming_kmeans(
            lambda c: Y[ranges[c][0]:ranges[c][1]], plan.nchunks, plan.k,
            rounds=plan.kmeans_rounds, seed=plan.seed)

    stats = dict(op.stats_snapshot(), path="fused", chunks=plan.nchunks,
                 points_bytes=int(x.nbytes),
                 lanczos_steps=plan.num_lanczos_steps(),
                 block_size=b, block_steps=block_steps,
                 build_s=round(sp_build.duration_s, 4),
                 eigensolve_s=round(sp_eig.duration_s, 4),
                 kmeans_s=round(sp_km.duration_s, 4))
    obs.absorb_stats("engine", stats)
    return JobResult(labels=labels, embedding=Y,
                     eigenvalues=np.asarray(evals), centers=centers,
                     sigma=sigma, graph=None, stats=stats)


def _out_of_memory(e: BaseException) -> bool:
    """Host RAM or device HBM ran out.  Mosaic reports a kernel whose
    tiles overflow VMEM as RESOURCE_EXHAUSTED too, at compile time: that
    is a schedule fault, not a job too large for the chip."""
    if isinstance(e, MemoryError):
        return True
    msg = str(e)
    return "RESOURCE_EXHAUSTED" in msg and "vmem" not in msg.lower()


def run_job(plan: JobPlan, reader) -> JobResult:
    """Full out-of-core pipeline: dependency-scheduled graph build,
    shard-streaming block Lanczos, chunked mini-batch k-means.
    ``reader[c]`` must yield the (rows, d) point chunk for range
    ``plan.ranges[c]``.

    Phase 1 honours the planner's routing (:func:`repro.engine.plan.
    route_path`): jobs whose points fit the memory budget but whose dense
    similarity does not take the fused matrix-free path instead of
    spilling CSR shards (``plan.path`` forces either way).

    On the ooc path the eigensolve is the *block* recurrence: each block
    step pulls every CSR shard from the store exactly once and amortizes
    it over the b-wide block, so the same Krylov dimension costs ~1/b the
    shard loads (and spill-reloads) of the single-vector iteration.  The
    eigensolver's start block is drawn WHILE the reduce tail drains
    (bitwise-identical to drawing it after — same key, same shape), and
    the graph's prefetch pool is shut down before returning, so a job
    never strands background threads."""
    fallback = None
    if plan.path == "fused":
        return _run_fused(plan, reader)
    if plan.path == "auto":         # probe d only when routing needs it
        d = int(np.asarray(reader[0]).shape[1])
        if route_path(plan, d) == "fused":
            try:
                return _run_fused(plan, reader)
            except (MemoryError, jax.errors.JaxRuntimeError) as e:
                # graceful degradation: an auto-routed fused job that runs
                # out of host or device memory falls back to the ooc
                # pipeline.  Any other error propagates, as on an
                # explicitly forced path: a kernel that fails to lower or
                # compile is a fault, and rerouting would hide it behind a
                # slow, correct run
                if not _out_of_memory(e):
                    raise
                obs.counter("engine.path_fallbacks").inc()
                fallback = f"fused->ooc ({type(e).__name__})"

    key = jax.random.PRNGKey(plan.seed)
    _, k_lan, _k_km = jax.random.split(key, 3)
    b = plan.eff_block_size()
    block_steps = plan.num_block_steps()
    seed_box: Dict = {}

    def _warm_start():
        # exactly the draw lz.init_block_state would make (same key,
        # shape, dtype -> bitwise-identical eigensolve), issued while the
        # reduce tail is still draining on the task pool
        seed_box["V0"] = jax.block_until_ready(
            jax.random.normal(k_lan, (b, plan.n), jnp.float32))

    graph, sigma = build_graph(reader, plan, overlap_work=_warm_start)
    op = make_normalized_operator(graph)

    with obs.span("engine.eigensolve", path="ooc",
                  block_steps=block_steps) as sp_eig:
        state = lz.block_lanczos(op.matmat, plan.n, block_steps, k_lan,
                                 block_size=b, V0=seed_box["V0"],
                                 host_matmat=op.host_matmat)
        evals, Z = lz.block_topk_of_shifted(state, plan.k)
        jax.block_until_ready(Z)

    Y = np.asarray(km.normalize_rows(Z))
    ranges = plan.ranges
    with obs.span("engine.kmeans", path="ooc") as sp_km:
        labels, centers = skm.streaming_kmeans(
            lambda c: Y[ranges[c][0]:ranges[c][1]], plan.nchunks, plan.k,
            rounds=plan.kmeans_rounds, seed=plan.seed)

    stats = dict(graph.stats_snapshot(), path="ooc",
                 lanczos_steps=plan.num_lanczos_steps(),
                 block_size=b, block_steps=block_steps,
                 matrix_passes=block_steps,
                 eigensolve_s=round(sp_eig.duration_s, 4),
                 kmeans_s=round(sp_km.duration_s, 4))
    if fallback is not None:
        stats["path_fallback"] = fallback
    obs.absorb_stats("engine", stats)
    graph.close()                   # no stray prefetch threads after a job
    return JobResult(labels=labels, embedding=Y,
                     eigenvalues=np.asarray(evals), centers=centers,
                     sigma=sigma, graph=graph, stats=stats)
