"""The unified estimator: one entry point, three pluggable phases.

    est = SpectralClustering(k=3, affinity="triangular",
                             eigensolver="lanczos", assigner="lloyd")
    est.fit(x)                 # points (n, d)
    est.labels_                # (n,) cluster ids, original point order
    est.predict(x_new)         # nearest-center assignment of new points
                               # in embedding space (Nystrom extension)

``fit`` runs the paper's three phases — similarity, eigendecomposition,
k-means — each selected by a registry string; any affinity composes with
any eigensolver and any assigner because they meet at the
:class:`~repro.cluster.operator.NormalizedOperator` interface.

RNG discipline matches the legacy ``spectral.fit`` exactly (one PRNGKey
split three ways), so ``SpectralClustering(affinity="triangular",
eigensolver="lanczos", assigner="lloyd").fit(x)`` reproduces the old
pipeline bit-for-bit.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.cluster import serving
from repro.cluster.affinity import AFFINITIES, point_dtype
from repro.cluster.assigners import ASSIGNERS
from repro.cluster.eigensolvers import EIGENSOLVERS
from repro.cluster.operator import SpectralResult
from repro.core import kmeans as km, laplacian as lp, similarity as sim
from repro.distrib import mesh_utils
from repro.precision import matmul

# on-disk model layout version (est.save / SpectralClustering.load)
MODEL_FORMAT = 1
_MODEL_ARRAYS = ("train_x", "eigvecs", "inv_sqrt", "eigenvalues", "centers",
                 "sigma", "labels", "embedding")


class SpectralClustering:
    """Parallel spectral clustering with pluggable phase backends.

    Parameters
    ----------
    k:              number of clusters (and embedding dimensions).
    affinity:       name in :data:`~repro.cluster.AFFINITIES`
                    ("dense" | "triangular" | "compact" | "precomputed"
                    | "graph" | "knn-topt" | "ooc-topt" | "fused-rbf").
                    With "precomputed", ``fit(S)`` treats its argument as
                    the dense (n, n) similarity matrix; with "graph", as
                    a ``graph_file.SparseAdjacency`` (``fit_graph``);
                    "ooc-topt" builds the graph
                    out-of-core through ``repro.engine``; "fused-rbf"
                    never materializes the similarity at all (O(n*d)
                    affinity memory, see ``compute_dtype``).
    eigensolver:    name in :data:`~repro.cluster.EIGENSOLVERS`
                    ("lanczos" | "block-lanczos" | "chebdav" | "eigh").
    assigner:       name in :data:`~repro.cluster.ASSIGNERS`
                    ("lloyd" | "minibatch" | "streaming").
    sigma:          RBF bandwidth; None = median heuristic.
    lanczos_steps:  None = max(4k, 32), capped below n.  For
                    "block-lanczos" this is the target Krylov dimension:
                    the solver runs ceil(steps / block_size) block steps
                    (same subspace, ~1/block_size the matrix passes).
    block_size:     block width b for "block-lanczos" / "chebdav"
                    (None = 8 for block-lanczos, max(2, k) for chebdav);
                    "lanczos" is "block-lanczos" at b = 1.
    cheb_degree:    Chebyshev filter degree for "chebdav".
    sparsify_t:     top-t per row for the "knn-topt" / "ooc-topt"
                    affinities (None = max(k + 2, 10)).
    compute_dtype:  MXU product precision inside the "fused-rbf" kernel:
                    None/"float32" (default) or "bfloat16"/"bf16"
                    (halved MXU operand volume; accumulation stays f32
                    either way, so only the similarity entries lose
                    precision).  Also read by the fused transform path.
    schedule:       kernel schedule for the Pallas-backed paths
                    (fused-rbf affinity, knn-topt similarity, fused
                    transform): None/"default" (the built-in tiles),
                    "auto" (consult the persistent schedule cache filled
                    by ``repro.tune.autotune`` — falls back to the
                    default on a miss), or an explicit
                    :class:`repro.tune.Schedule` / dict of its fields.
                    The schedule actually used is recorded in
                    ``info_["schedule"]`` (fit) and
                    ``info_["transform"]["schedule"]`` (transform).
    transform_path: out-of-sample extension path for transform/predict:
                    "auto" (default — the (m, n) kernel's bytes against
                    ``memory_budget`` or a 64 MiB default decide, like
                    ``engine.route_path``), "dense" (materialize the
                    query-vs-train kernel) or "fused" (matrix-free
                    dual-output kernel, O((m+n)*d + n*k) memory).
    chunk_size:     rows per chunk for the out-of-core "ooc-topt"
                    affinity and "streaming" assigner (None = 1024/4096).
    memory_budget:  engine shard-store RAM budget in bytes
                    (None = unlimited, nothing spills to disk).
    spill_dir:      where the engine spills shards (None = temp dir).
    workers:        engine task-pool width for the "ooc-topt" graph build
                    (map/shuffle/reduce run dependency-driven on this
                    many threads; 1 = sequential order, results are
                    bitwise-identical at any width).
    prefetch_depth: shard readahead window of the engine's streaming
                    matmat (how many upcoming CSR shards are fetched
                    concurrently while the current one multiplies).
    max_retries:    engine per-task re-execution budget for the
                    "ooc-topt" build (failed attempts retry with
                    exponential backoff; retried results are
                    bitwise-identical).
    speculation_factor: engine straggler threshold k — a running task
                    whose wall exceeds k x the stage's running-median
                    wall gets one speculative backup attempt (0 = off).
    stage_timeout_s: per-stage deadline for the engine build; on expiry
                    the job cancels queued tasks, abandons hung attempts
                    (the deadline bounds the fit's wall time even when a
                    task sticks in blocked I/O) and the fit FALLS BACK to
                    the in-memory "knn-topt" affinity (the same top-t
                    graph, no spilling) instead of failing.
    faults:         optional ``engine.FaultPlan`` for deterministic
                    fault injection (tests/benchmarks; None = no-op).
    mesh:           device mesh; None = all local devices.

    Fitted attributes (original point order): ``labels_``, ``embedding_``,
    ``eigenvalues_``, ``centers_``, ``sigma_``, ``info_``, ``result_``.
    """

    def __init__(self, k: int = 8, *, affinity: str = "triangular",
                 eigensolver: str = "lanczos", assigner: str = "lloyd",
                 sigma: float | None = None, lanczos_steps: int | None = None,
                 block_size: int | None = None, cheb_degree: int = 12,
                 kmeans_iters: int = 50, sparsify_t: int | None = None,
                 compute_dtype: Any = None, schedule: Any = None,
                 transform_path: str = "auto",
                 minibatch_size: int = 256, chunk_size: int | None = None,
                 memory_budget: int | None = None,
                 spill_dir: str | None = None,
                 workers: int = 1, prefetch_depth: int = 2,
                 max_retries: int = 2, speculation_factor: float = 0.0,
                 stage_timeout_s: float | None = None, faults: Any = None,
                 seed: int = 0,
                 dtype: Any = jnp.float32, mesh: Optional[Mesh] = None):
        # Resolve backends eagerly so a typo fails at construction, not
        # after an expensive similarity phase.
        self._affinity_fn = AFFINITIES.get(affinity)
        self._eigensolver_fn = EIGENSOLVERS.get(eigensolver)
        self._assigner_fn = ASSIGNERS.get(assigner)
        if cheb_degree < 1:
            raise ValueError(
                f"cheb_degree must be >= 1, got {cheb_degree}")
        self.k = k
        self.affinity = affinity
        self.eigensolver = eigensolver
        self.assigner = assigner
        self.sigma = sigma
        self.lanczos_steps = lanczos_steps
        self.block_size = block_size
        self.cheb_degree = cheb_degree
        self.kmeans_iters = kmeans_iters
        self.sparsify_t = sparsify_t
        # validate eagerly (same philosophy as the registry lookups)
        from repro.kernels.fused_rbf_matmat import resolve_compute_dtype
        resolve_compute_dtype(compute_dtype)
        self.compute_dtype = compute_dtype
        from repro.tune.schedule import validate_spec
        self.schedule = validate_spec(schedule)
        serving.check_transform_path(transform_path)
        self.transform_path = transform_path
        self._transform_cache: dict = {}
        self.minibatch_size = minibatch_size
        self.chunk_size = chunk_size
        self.memory_budget = memory_budget
        self.spill_dir = spill_dir
        if workers < 1:
            raise ValueError(f"workers must be >= 1, got {workers}")
        if prefetch_depth < 1:
            raise ValueError(
                f"prefetch_depth must be >= 1, got {prefetch_depth}")
        self.workers = workers
        self.prefetch_depth = prefetch_depth
        if max_retries < 0:
            raise ValueError(f"max_retries must be >= 0, got {max_retries}")
        if speculation_factor < 0:
            raise ValueError(f"speculation_factor must be >= 0 (0 = off), "
                             f"got {speculation_factor}")
        if stage_timeout_s is not None and stage_timeout_s <= 0:
            raise ValueError(f"stage_timeout_s must be positive seconds or "
                             f"None, got {stage_timeout_s}")
        self.max_retries = max_retries
        self.speculation_factor = speculation_factor
        self.stage_timeout_s = stage_timeout_s
        self.faults = faults
        self.seed = seed
        self.dtype = dtype
        self.mesh = mesh
        self.result_: SpectralResult | None = None

    # -- configuration helpers ------------------------------------------------

    def num_lanczos_steps(self, n: int) -> int:
        m = self.lanczos_steps or max(4 * self.k, 32)
        return int(min(m, n - 1))

    def num_block_size(self, n: int | None = None) -> int:
        if self.eigensolver == "lanczos":
            return 1
        if self.block_size is not None:
            if self.block_size <= 0:
                raise ValueError(
                    f"block_size must be positive, got {self.block_size}")
            b = int(self.block_size)
        else:
            b = 8 if self.eigensolver == "block-lanczos" else max(2, self.k)
        return b if n is None else max(1, min(b, n))

    def num_block_steps(self, n: int) -> int:
        """Block steps covering the same Krylov dimension as the
        single-vector iteration would (ceil division by the block width),
        so accuracy is comparable at ~1/b the matrix passes."""
        b = self.num_block_size(n)
        return max(1, -(-self.num_lanczos_steps(n) // b))

    def _start_request(self, n: int, key) -> dict:
        """``{"start": (key, b)}`` where the affinity backend can return
        the eigensolver's first block product with its degrees (its
        ``folds_start`` attribute) and the eigensolver takes a start block
        of width b (its ``start_width(est, n)`` attribute); else ``{}``."""
        width = getattr(self._eigensolver_fn, "start_width", None)
        if width is None or not getattr(self._affinity_fn, "folds_start",
                                        False):
            return {}
        return {"start": (key, width(self, n))}

    def _mesh(self) -> Mesh:
        return self.mesh or mesh_utils.local_mesh("rows")

    # -- fitting --------------------------------------------------------------

    def fit(self, x: jax.Array, checkpointer: Any = None) -> "SpectralClustering":
        """Cluster points (n, d) — or, with ``affinity="precomputed"``, a
        similarity matrix (n, n), and with ``affinity="graph"`` a
        graph's nonzero list.  Returns ``self``.  Points are cast to
        the dtype the affinity backend takes them in
        (``affinity.point_dtype``): ``dtype``, except bfloat16 points
        under ``affinity="fused-rbf"``, which stay bfloat16."""
        if self.affinity == "precomputed":
            return self.fit_affinity(x, checkpointer=checkpointer)
        if self.affinity == "graph":
            return self.fit_graph(x, checkpointer=checkpointer)
        mesh = self._mesh()
        phases: dict = {}
        with obs.span("fit", affinity=self.affinity,
                      eigensolver=self.eigensolver, assigner=self.assigner,
                      n=int(x.shape[0])) as sp_fit:
            with obs.span("fit.affinity", backend=self.affinity) as sp_aff:
                x = jnp.asarray(x)
                x = x.astype(point_dtype(self._affinity_fn, x.dtype,
                                         self.dtype))
                key = jax.random.PRNGKey(self.seed)
                _k_eig, k_lan, k_km = jax.random.split(key, 3)
                sigma = jnp.asarray(self.sigma, self.dtype) \
                    if self.sigma is not None else sim.median_sigma(x)
                op = self._affinity_fn(
                    self, x, sigma, mesh,
                    **self._start_request(int(x.shape[0]), k_lan))
                jax.block_until_ready((op.inv_sqrt, op.valid))
            phases["affinity"] = sp_aff
            if checkpointer is not None:
                checkpointer.save_phase("similarity", {"sigma": sigma})
            self._finish(op, sigma, k_lan, k_km, mesh, checkpointer,
                         train_x=x, affinity_used=self.affinity,
                         phases=phases)
        self._record_obs(sp_fit, phases)
        return self

    def fit_affinity(self, S: jax.Array,
                     checkpointer: Any = None) -> "SpectralClustering":
        """Cluster from a precomputed dense (n, n) similarity/adjacency
        matrix, regardless of ``self.affinity``."""
        return self._fit_given("precomputed", S, int(S.shape[0]),
                               checkpointer)

    def fit_graph(self, adj, checkpointer: Any = None
                  ) -> "SpectralClustering":
        """Cluster a graph's vertices (the paper's §5 graph dataset) from
        its adjacency's nonzero list, a
        :class:`~repro.data.graph_file.SparseAdjacency` on the host or
        the device, regardless of ``self.affinity``: the ``graph``
        affinity, which never builds the (n, n) matrix."""
        from repro.data.graph_file import SparseAdjacency
        if not isinstance(adj, SparseAdjacency):
            raise ValueError(
                f"a graph fit takes a graph_file.SparseAdjacency "
                f"(adjacency_sparse), got {type(adj).__name__}")
        return self._fit_given("graph", adj, adj.n, checkpointer)

    def _fit_given(self, backend: str, arg, n: int,
                   checkpointer: Any) -> "SpectralClustering":
        """A fit whose affinity is given, not computed from points: no
        sigma, no training points to extend to new ones."""
        mesh = self._mesh()
        phases: dict = {}
        with obs.span("fit", affinity=backend,
                      eigensolver=self.eigensolver, assigner=self.assigner,
                      n=n) as sp_fit:
            with obs.span("fit.affinity", backend=backend) as sp_aff:
                key = jax.random.PRNGKey(self.seed)
                _k_eig, k_lan, k_km = jax.random.split(key, 3)
                op = AFFINITIES.get(backend)(self, arg, None, mesh)
                jax.block_until_ready((op.inv_sqrt, op.valid))
            phases["affinity"] = sp_aff
            self._finish(op, jnp.asarray(0.0, self.dtype), k_lan, k_km,
                         mesh, checkpointer, train_x=None,
                         affinity_used=backend, phases=phases)
        self._record_obs(sp_fit, phases)
        return self

    def fit_predict(self, x: jax.Array) -> jax.Array:
        return self.fit(x).labels_

    def _finish(self, op, sigma, k_lan, k_km, mesh, checkpointer, train_x,
                affinity_used, phases=None):
        phases = phases if phases is not None else {}
        # a reused operator starts a fresh counter window here (fresh
        # operators are already at their post-build baseline: no-op)
        op.reset_stats()
        with obs.span("fit.eigensolve", backend=self.eigensolver) as sp_eig:
            evals, Z, info = self._eigensolver_fn(self, op, k_lan)
            jax.block_until_ready(Z)
        phases["eigensolve"] = sp_eig
        if checkpointer is not None:
            checkpointer.save_phase("eigen", {"eigenvalues": evals})
        with obs.span("fit.assign", backend=self.assigner) as sp_asg:
            Y = km.normalize_rows(Z) * op.valid[:, None]
            Y = jax.lax.with_sharding_constraint(
                Y, NamedSharding(mesh, P(mesh_utils.flat_axes(mesh), None)))
            labels_pad, centers = self._assigner_fn(self, Y, op.valid, k_km,
                                                    mesh)
            labels_unp = op.unpermute(labels_pad)
            emb_unp = op.unpermute(Y)
            jax.block_until_ready(labels_unp)
        phases["assign"] = sp_asg
        if checkpointer is not None:
            checkpointer.save_phase("kmeans", {"centers": centers})

        self.labels_ = labels_unp
        self.embedding_ = emb_unp
        self.eigenvalues_ = evals
        self.centers_ = centers
        self.sigma_ = sigma
        self.info_ = dict(info, affinity=affinity_used,
                          eigensolver=self.eigensolver,
                          assigner=self.assigner, n_pad=op.n_pad)
        op_stats = op.stats_snapshot()
        if op_stats:
            self.info_["engine"] = op_stats
        fb = getattr(self, "_affinity_fallback", None)
        if fb is not None:             # graceful-degradation audit trail
            self.info_["affinity_fallback"] = fb
            self._affinity_fallback = None
        # release backend worker resources (the engine's shard-prefetch
        # pool) — a fit must not strand background threads
        if getattr(op, "close", None) is not None:
            op.close()
        # surface the kernel schedule that actually ran: the fused
        # operator reports its resolved schedule (incl. "auto" cache
        # hits); other affinities record the estimator-level request
        if op_stats and "schedule" in op_stats:
            self.info_["schedule"] = {
                "value": op_stats["schedule"],
                "source": op_stats.get("schedule_source", "default")}
        elif self.schedule is not None:
            from repro.tune.schedule import as_schedule
            s = None if self.schedule == "auto" \
                else as_schedule(self.schedule)
            self.info_["schedule"] = {
                "value": "auto" if s is None else s.to_dict(),
                "source": "requested"}
        # Nystrom-extension state for transform()/predict(): unnormalized
        # eigenvector rows and D^{-1/2}, both in original point order.
        self._train_x = train_x
        self._eigvecs = op.unpermute(Z)
        self._inv_sqrt = op.unpermute(op.inv_sqrt)
        self.result_ = SpectralResult(
            labels=self.labels_, embedding=self.embedding_,
            eigenvalues=evals, centers=centers, sigma=sigma,
            info=self.info_)
        return self

    def _record_obs(self, fit_span, phases):
        """Publish ``info_["obs"]`` (phase walls + coverage + counters)
        and mirror the numeric fit stats into the process registry."""
        counters: dict = {}
        info = getattr(self, "info_", None) or {}
        for k, v in list(info.items()) + list((info.get("engine")
                                               or {}).items()):
            if hasattr(v, "item") and not isinstance(v, (bool, int, float,
                                                         str)):
                try:
                    v = v.item()
                except Exception:
                    continue
            if isinstance(v, bool) or not isinstance(v, (int, float)):
                continue
            counters.setdefault(k, v)
        self.info_["obs"] = obs.fit_obs(fit_span, phases, counters=counters)
        obs.absorb_stats("fit", counters)

    # -- out-of-sample extension ----------------------------------------------

    def transform(self, x: jax.Array) -> jax.Array:
        """Embed new points (m, d) into the fitted spectral space.

        Nystrom extension: z_j(x) = (1/mu_j) sum_i N(x, i) z_j(i) with
        N the degree-normalized kernel and mu_j = 1 - lambda_j the
        eigenvalue of N; rows are then unit-normalized like the training
        embedding.  Requires a feature-space fit (not "precomputed").

        Routed per ``transform_path``: the dense path materializes the
        (m, n) query-vs-train kernel (fine for small problems); the fused
        path streams it through the dual-output Pallas kernel and never
        builds it (O((m+n)*d + n*k) memory).  Both agree to <= 1e-4 in
        f32; the route taken is recorded in ``info_["transform"]``.
        """
        self._check_fitted()
        if self._train_x is None:
            raise ValueError(
                "transform/predict need the training points; an estimator "
                "fitted from a precomputed similarity matrix cannot embed "
                "new points")
        x = jnp.asarray(x, self.dtype)
        m, n = int(x.shape[0]), int(self._train_x.shape[0])
        path = serving.route_transform(n, m, path=self.transform_path,
                                       memory_budget=self.memory_budget)
        mu = serving.shifted_mu(self.eigenvalues_)
        with obs.span("transform", path=path, m=m, n=n):
            if path == "dense":
                K = sim.rbf_kernel(x, self._train_x, self.sigma_)
                O = matmul(K, self._inv_sqrt[:, None] * self._eigvecs)
                emb = serving.extension_from_product(O, jnp.sum(K, axis=1),
                                                     mu)
                peak = m * n * 4
            else:
                sched_info: dict = {}
                emb = serving.fused_transform(
                    x, self._train_x, self._eigvecs, self._inv_sqrt,
                    self.sigma_, mu, mesh=self._mesh(),
                    compute_dtype=self.compute_dtype,
                    schedule=getattr(self, "schedule", None),
                    _cache=self._transform_cache, _info=sched_info)
                peak = serving.transform_peak_bytes(
                    m, n, int(x.shape[1]), self.k,
                    mesh_size=mesh_utils.mesh_size(self._mesh()))
        obs.counter("transform.calls", path=path).inc()
        self.info_.setdefault("transform", {}).update(
            path=path, m=m, peak_bytes=int(peak),
            dense_equiv_bytes=m * n * 4)
        if path == "fused" and sched_info:
            self.info_["transform"].update(sched_info)
        return emb

    def predict(self, x: jax.Array) -> jax.Array:
        """Nearest-center cluster assignment of new points in embedding
        space (the fitted centers are the reference)."""
        with obs.span("predict", m=int(x.shape[0])):
            return km.assign(self.transform(x), self.centers_)

    def _check_fitted(self):
        if self.result_ is None:
            raise ValueError("this SpectralClustering instance is not "
                             "fitted yet; call fit() first")

    # -- persistence ----------------------------------------------------------

    def save(self, directory: str) -> str:
        """Persist the fitted model (the Nystrom serving state: training
        points, eigenvector block, D^{-1/2}, eigenvalues, centers, sigma,
        plus labels/embedding) to ``directory`` — one ``CheckpointManager``
        npz of logical, unsharded arrays plus a ``config.json`` of the
        constructor parameters.  Restore with
        :meth:`SpectralClustering.load`, on any device count (elastic:
        arrays re-place onto whatever mesh the loading process has)."""
        import json
        import os

        from repro.checkpoint import CheckpointManager
        from repro.kernels.fused_rbf_matmat import resolve_compute_dtype

        self._check_fitted()
        if self._train_x is None:
            raise ValueError(
                "cannot save a model fitted from a precomputed similarity "
                "matrix; transform/predict would have no training points")
        os.makedirs(directory, exist_ok=True)
        # bf16 points are saved widened (exactly): npz has no bfloat16
        state = {"train_x": jnp.asarray(self._train_x, jnp.float32),
                 "eigvecs": self._eigvecs,
                 "inv_sqrt": self._inv_sqrt,
                 "eigenvalues": self.eigenvalues_, "centers": self.centers_,
                 "sigma": self.sigma_, "labels": self.labels_,
                 "embedding": self.embedding_}
        mgr = CheckpointManager(directory, keep=1, async_write=False)
        path = mgr.save(0, state, name="model")
        cfg = {
            "format": MODEL_FORMAT,
            "params": {
                "k": self.k, "affinity": self.affinity,
                "eigensolver": self.eigensolver, "assigner": self.assigner,
                "sigma": self.sigma, "lanczos_steps": self.lanczos_steps,
                "block_size": self.block_size,
                "cheb_degree": self.cheb_degree,
                "kmeans_iters": self.kmeans_iters,
                "sparsify_t": self.sparsify_t,
                # normalize to the string form (the constructor may have
                # been handed a dtype object, which JSON can't encode)
                "compute_dtype": None if self.compute_dtype is None else
                jnp.dtype(resolve_compute_dtype(self.compute_dtype)).name,
                # Schedule objects serialize to their field dict; strings
                # ("auto"/"default") and None pass through as-is
                "schedule": (self.schedule.to_dict()
                             if hasattr(self.schedule, "to_dict")
                             else self.schedule),
                "transform_path": self.transform_path,
                "minibatch_size": self.minibatch_size,
                "chunk_size": self.chunk_size,
                "memory_budget": self.memory_budget,
                "workers": self.workers,
                "prefetch_depth": self.prefetch_depth,
                "seed": self.seed, "dtype": jnp.dtype(self.dtype).name,
            },
            "fitted": {"n": int(self._train_x.shape[0]),
                       "d": int(self._train_x.shape[1]),
                       "info": {k: v for k, v in self.info_.items()
                                if isinstance(v, (str, int, float))}},
        }
        tmp = os.path.join(directory, "config.json.tmp")
        with open(tmp, "w") as f:
            json.dump(cfg, f, indent=2)
        os.replace(tmp, os.path.join(directory, "config.json"))
        return path

    @classmethod
    def load(cls, directory: str, *,
             mesh: Optional[Mesh] = None) -> "SpectralClustering":
        """Rebuild a fitted estimator from :meth:`save` output.  The
        restored model predicts bitwise-identically to the estimator that
        was saved (same routing, same kernel passes); ``mesh`` defaults to
        all local devices, whatever their count was at save time."""
        import json
        import os

        from repro.checkpoint import CheckpointManager

        with open(os.path.join(directory, "config.json")) as f:
            cfg = json.load(f)
        if cfg.get("format") != MODEL_FORMAT:
            raise ValueError(
                f"unsupported model format {cfg.get('format')!r} in "
                f"{directory} (this build reads format {MODEL_FORMAT})")
        params = dict(cfg["params"])
        params["dtype"] = jnp.dtype(params["dtype"])
        est = cls(mesh=mesh, **params)
        mgr = CheckpointManager(directory, keep=1, async_write=False)
        # the template only supplies the pytree structure; leaf values and
        # shapes come from the checkpoint itself
        state = mgr.restore({name: 0 for name in _MODEL_ARRAYS},
                            name="model")
        est._train_x = jnp.asarray(state["train_x"], est.dtype)
        est._eigvecs = state["eigvecs"]
        est._inv_sqrt = state["inv_sqrt"]
        est.eigenvalues_ = state["eigenvalues"]
        est.centers_ = state["centers"]
        est.sigma_ = state["sigma"]
        est.labels_ = state["labels"]
        est.embedding_ = state["embedding"]
        est.info_ = dict(cfg["fitted"].get("info", {}))
        est.result_ = SpectralResult(
            labels=est.labels_, embedding=est.embedding_,
            eigenvalues=est.eigenvalues_, centers=est.centers_,
            sigma=est.sigma_, info=est.info_)
        return est
