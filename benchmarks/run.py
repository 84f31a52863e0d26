"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.

  table1_phases     paper Table 5-1: the three pipeline phases.  Measured
                    single-worker wall time at n=4096, plus the
                    balanced-schedule projection T(m) for m workers
                    (tiles-per-device model validated by the schedule
                    property tests; wall speedup is unmeasurable on one
                    CPU core, and the projection is labeled as such).
  fig5_speedup      paper Fig. 5 trend: projected total speedup vs m,
                    including the comm term that produces the paper's
                    critical-machine-count plateau.
  rings_quality     paper §3.1 claim: spectral vs k-means on non-convex data.
  lanczos_residual  eigensolver quality vs iteration count.
  assigner_backends registry assigners: full Lloyd vs mini-batch rounds.
  kernels           Pallas kernel wrappers (interpret) vs jnp oracle.
  engine_ooc        the out-of-core MapReduce engine: (a) label agreement
                    vs the in-memory knn-topt backend on a shared
                    reference problem, (b) clustering an n whose dense
                    (n, n) similarity would not fit the shard-store
                    budget — shards demonstrably spilled to disk.
  eigensolver_sweep lanczos vs block-lanczos vs chebdav on the dense and
                    out-of-core paths at n=4096: matrix passes per
                    eigenpair, wall time, shard-store loads per
                    eigensolve, and chebdav-vs-eigh label agreement on
                    the paper config.  Writes BENCH_eigensolvers.json.
  fused_sweep       dense vs fused-rbf vs ooc across an n sweep: wall
                    time, peak affinity-stage bytes, ARI vs dense/eigh
                    labels, and the engine's prefetch hit counters under
                    a spill-forcing budget.  Writes BENCH_fused.json.
  async_sweep       the async engine vs its own sequential ancestor at
                    n=4096 under a spill-forcing budget: pipelined build
                    + prefetched/double-buffered eigensolve + async spill
                    writes vs the PR-7 schedule (workers=1, synchronous
                    spills, per-column scatter), plus prefetch hit rate,
                    ooc-vs-fused matmat cost, bitwise scheduler parity
                    and the dense-oracle ARI.  Writes BENCH_async.json.
  serve_sweep       the serving path: fused vs dense out-of-sample
                    transform (wall + peak bytes + label parity) at
                    m queries vs an n=8192 model, save/load round-trip
                    bitwise predict parity, and the batched predict
                    service's throughput.  Writes BENCH_serve.json.
  chaos_sweep       fault-tolerance acceptance: injected task failures,
                    spill corruption and stragglers at n=4096 must
                    recover to labels bitwise-equal to the fault-free
                    run (ARI == 1); the resilience machinery costs <= 3%
                    build wall when nothing fails; and the serve path
                    under 2x overload sheds with typed rejections while
                    admitted p99 stays <= 2x the unloaded p99.  Writes
                    BENCH_chaos.json.

Run ``python benchmarks/run.py [mode ...]`` — no mode runs the full
default suite; ``eigensolver_sweep`` / ``fused_sweep`` run just the
sweeps.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.cluster import SpectralClustering, ari
from repro.core import kmeans as km
from repro.core import lanczos as lz
from repro.core import laplacian as lp
from repro.core import similarity as sim
from repro.data import synthetic

ROWS: list[tuple[str, float, str]] = []


def row(name: str, us: float, derived: str = ""):
    ROWS.append((name, us, derived))
    print(f"{name},{us:.1f},{derived}", flush=True)


def _timeit(fn, *args, warmup=1, iters=3):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6, out


# ---------------------------------------------------------------------------

def table1_phases(n: int = 4096, k: int = 8):
    """Measured phase times (m=1) + balanced-schedule projections."""
    pts, _ = synthetic.blobs(n, k, dim=8, seed=0)
    x = jnp.asarray(pts)

    sim_fn = jax.jit(lambda a: sim.dense_similarity(a, 1.0))
    us_sim, S = _timeit(sim_fn, x)
    row("table1/similarity_m1", us_sim, f"n={n}")

    mm = lp.make_dense_shifted_matmat(S)
    lan_fn = jax.jit(lambda s: lz.block_run(mm, s, 8))
    state0 = lz.init_block_state(n, 64, jax.random.PRNGKey(0), 1)
    us_lan8, state = _timeit(lan_fn, state0)
    us_lan = us_lan8 / 8 * 64          # 64 iterations total
    row("table1/lanczos_m1", us_lan, "64 iters")

    evals, Z = lz.block_topk_of_shifted(lz.block_run(mm, state0, 64), k)
    Y = km.normalize_rows(Z)
    c0 = km.kmeans_plusplus_init(Y, k, jax.random.PRNGKey(1))
    km_fn = jax.jit(lambda y, c: km.lloyd_step(
        y, jnp.ones((y.shape[0],)), km.KMeansState(
            it=jnp.zeros((), jnp.int32), centers=c, shift=jnp.asarray(jnp.inf))))
    us_km1, _ = _timeit(km_fn, Y, c0)
    us_km = us_km1 * 50
    row("table1/kmeans_m1", us_km, "50 rounds")

    # projection: the triangular schedule gives each of m workers (2m+1)
    # tiles out of 2m(2m+1)/2 upper tiles -> per-worker share (2m+1)/(2m)
    # of one row-block; lanczos matvec and kmeans shard 1/m.  The comm
    # term alpha*log2(m) is a collective-latency proxy (the paper's
    # critical-machine-count effect).
    alpha_us = 2000.0
    for m in (1, 2, 4, 6, 8, 10):
        t_sim = us_sim * (2 * m + 1) / (2 * m) / m
        t_lan = us_lan / m + 64 * alpha_us * np.log2(max(m, 2))
        t_km = us_km / m + 50 * alpha_us * np.log2(max(m, 2))
        row(f"table1/projected_total_m{m}", t_sim + t_lan + t_km,
            f"sim={t_sim:.0f}us lan={t_lan:.0f}us km={t_km:.0f}us")


def fig5_speedup():
    """Paper Fig. 5: speedup flattens past the critical machine count."""
    base = None
    for m in (1, 2, 4, 6, 8, 10):
        work = 1e6 / m
        comm = 12000.0 * np.log2(max(m, 2)) * 10
        total = work + comm
        if base is None:
            base = total
        row(f"fig5/speedup_m{m}", total, f"speedup={base / total:.2f}")


def rings_quality(n: int = 400):
    pts, truth = synthetic.rings(n, 2, seed=0)
    est = SpectralClustering(k=2, affinity="dense", eigensolver="eigh",
                             sigma=0.25, lanczos_steps=48)
    t0 = time.perf_counter()
    est.fit(jnp.asarray(pts))
    us = (time.perf_counter() - t0) * 1e6
    labels = np.asarray(est.labels_)
    acc_s = max(np.mean(labels == truth), np.mean(labels == 1 - truth))
    kl, _ = km.kmeans(jnp.asarray(pts), 2, jax.random.PRNGKey(0))
    kl = np.asarray(kl)
    acc_k = max(np.mean(kl == truth), np.mean(kl == 1 - truth))
    row("rings/spectral", us, f"acc={acc_s:.3f}")
    row("rings/kmeans_baseline", 0.0, f"acc={acc_k:.3f}")


def lanczos_residual(n: int = 512):
    pts, _ = synthetic.blobs(n, 4, seed=3)
    S = sim.dense_similarity(jnp.asarray(pts), 1.0)
    mm = lp.make_dense_shifted_matmat(S)
    for steps in (8, 16, 32, 64):
        t0 = time.perf_counter()
        state = lz.block_lanczos(mm, n, steps, jax.random.PRNGKey(0),
                                 block_size=1)
        vals, vecs = lz.block_topk_of_shifted(state, 4)
        us = (time.perf_counter() - t0) * 1e6
        res = float(jnp.max(lz.residuals(mm, vals, vecs, shift=2.0)))
        row(f"lanczos/steps{steps}", us, f"max_residual={res:.2e}")


def assigner_backends(n: int = 8192, k: int = 8):
    """Registry assigners on one embedding: full Lloyd vs mini-batch.

    Mini-batch touches ``batch`` points per round instead of ``n`` — the
    large-n phase-3 backend of the estimator API."""
    y = jax.random.normal(jax.random.PRNGKey(0), (n, k))
    valid = jnp.ones((n,))
    key = jax.random.PRNGKey(1)
    c0 = km.kmeans_plusplus_init(y, k, key)

    lloyd = jax.jit(lambda y, c: km.lloyd_step(
        y, jnp.ones((y.shape[0],)), km.KMeansState(
            it=jnp.zeros((), jnp.int32), centers=c,
            shift=jnp.asarray(jnp.inf))).centers)
    us_l, _ = _timeit(lloyd, y, c0)
    row("assigner/lloyd_round", us_l, f"n={n}")

    mb = jax.jit(lambda y, v, c: km.minibatch_kmeans(
        y, v, k, jax.random.PRNGKey(2), iters=1, batch=256, centers0=c)[1])
    us_m, _ = _timeit(mb, y, valid, c0)
    row("assigner/minibatch_round", us_m, f"batch=256 speedup={us_l / us_m:.1f}x")


def kernels():
    from repro.kernels import ops, ref
    x = jax.random.normal(jax.random.PRNGKey(0), (256, 64))
    y = jax.random.normal(jax.random.PRNGKey(1), (256, 64))
    us, _ = _timeit(lambda: ops.rbf_similarity(x, y, 1.0, interpret=True))
    flops = 2 * 256 * 256 * 64
    row("kernels/rbf_similarity_interp", us, f"{flops / us / 1e3:.2f} GFLOP/s")
    us_r, _ = _timeit(lambda: ref.rbf_similarity(x, y, 1.0))
    row("kernels/rbf_similarity_ref", us_r, "jnp oracle")

    A = jax.random.normal(jax.random.PRNGKey(2), (1024, 1024))
    v = jax.random.normal(jax.random.PRNGKey(3), (1024,))
    us, _ = _timeit(lambda: ops.block_matvec(A, v, interpret=True))
    row("kernels/block_matvec_interp", us, f"{2 * 1024**2 / us / 1e3:.2f} GFLOP/s")
    us_r, _ = _timeit(lambda: ref.block_matvec(A, v))
    row("kernels/block_matvec_ref", us_r, "jnp oracle")

    V8 = jax.random.normal(jax.random.PRNGKey(6), (1024, 8))
    us, _ = _timeit(lambda: ops.block_matmat(A, V8, interpret=True))
    row("kernels/block_matmat_b8_interp", us,
        f"{2 * 8 * 1024**2 / us / 1e3:.2f} GFLOP/s (8 vectors, one A pass)")
    us_r, _ = _timeit(lambda: ref.block_matmat(A, V8))
    row("kernels/block_matmat_b8_ref", us_r, "jnp oracle")

    p = jax.random.normal(jax.random.PRNGKey(4), (2048, 16))
    c = jax.random.normal(jax.random.PRNGKey(5), (16, 16))
    us, _ = _timeit(lambda: ops.kmeans_assign(p, c, interpret=True))
    row("kernels/kmeans_assign_interp", us, "")
    us_r, _ = _timeit(lambda: ref.kmeans_assign(p, c))
    row("kernels/kmeans_assign_ref", us_r, "jnp oracle")


def engine_ooc(n_ref: int = 512, n_big: int = 4096, k: int = 3):
    """The out-of-core engine vs the in-memory dense-path ceiling.

    Quality: ooc-topt and knn-topt labels on the same reference points,
    scored with ARI (>= 0.95 is the engine's backend contract).  Scale:
    cluster ``n_big`` points under a shard-store budget that could hold at
    most a (budget/4)^0.5-point dense similarity — n_big is several times
    that ceiling, so finishing at all requires the shards to spill.
    """
    from repro import engine
    from repro.cluster import ari
    from repro.data.chunked import BlobChunks

    # (a) agreement on a shared reference problem (spread 0.8: weakly
    # connected blobs -> distinct small eigenvalues, stable eigenvectors)
    pts, _ = synthetic.blobs(n_ref, k, dim=4, spread=0.8, seed=0)
    t = 16
    ref = SpectralClustering(k=k, affinity="knn-topt", sparsify_t=t,
                             sigma=1.0, seed=0,
                             lanczos_steps=96).fit(jnp.asarray(pts))
    t0 = time.perf_counter()
    ooc = SpectralClustering(k=k, affinity="ooc-topt", sparsify_t=t,
                             sigma=1.0, seed=0, chunk_size=128,
                             lanczos_steps=96).fit(jnp.asarray(pts))
    us = (time.perf_counter() - t0) * 1e6
    a = ari(np.asarray(ref.labels_), np.asarray(ooc.labels_))
    row("engine/agreement_vs_knn_topt", us, f"n={n_ref} ari={a:.3f}")

    # (b) past the dense ceiling: budget fits at most a ~n_dense dense S
    budget = 1 << 19                              # 512 KiB shard-store RAM
    n_dense = int(np.sqrt(budget / 4))            # dense f32 S ceiling
    reader = BlobChunks(n_big, k, chunk_size=512, dim=4, spread=0.8, seed=0)
    # path="ooc" pins the classic spilling pipeline: this benchmark is the
    # CSR-shard demonstration (the auto router would send a fits-in-memory
    # point set to the fused path — that trade is fused_sweep's subject)
    plan = engine.JobPlan(n=n_big, chunk_size=512, t=t, k=k, sigma=1.0,
                          memory_budget=budget, lanczos_steps=96, seed=0,
                          path="ooc")
    t0 = time.perf_counter()
    res = engine.run_job(plan, reader)
    us = (time.perf_counter() - t0) * 1e6
    quality = ari(reader.all_labels(), res.labels)
    st = res.stats
    row("engine/ooc_beyond_dense_ceiling", us,
        f"n={n_big} ({n_big / n_dense:.1f}x dense ceiling {n_dense}) "
        f"budget={budget} spilled_shards={st['spilled_shards']} "
        f"bytes_spilled={st['store_bytes_spilled']} "
        f"peak_ram={st['store_peak_ram_bytes']} ari_vs_planted={quality:.3f}")
    assert st["store_bytes_spilled"] > 0, "budget was meant to force spills"


def eigensolver_sweep(n: int = 4096, k: int = 3, block_size: int = 8,
                      out_json: str = "BENCH_eigensolvers.json"):
    """lanczos vs block-lanczos vs chebdav: matrix passes per eigenpair,
    wall time, and (out-of-core) shard-store loads per eigensolve.

    The block contract this validates: at block width b the same Krylov
    dimension costs ~1/b the matrix passes, and on the engine path each
    pass pulls every CSR shard from the (spilling) store once per BLOCK
    instead of once per vector — so store loads per eigensolve drop by
    the same factor.
    """
    from repro import engine
    from repro.cluster.affinity import AFFINITIES
    from repro.cluster.eigensolvers import EIGENSOLVERS
    from repro.data.chunked import BlobChunks
    from repro.distrib import mesh_utils

    results: dict = {"n": n, "k": k, "block_size": block_size, "rows": []}
    solvers = ("lanczos", "block-lanczos", "chebdav")

    def solve(est, op, path, extra=None):
        key = jax.random.PRNGKey(1)
        t0 = time.perf_counter()
        evals, Z, info = EIGENSOLVERS.get(est.eigensolver)(est, op, key)
        jax.block_until_ready(Z)
        wall = time.perf_counter() - t0
        rec = {"path": path, "solver": est.eigensolver,
               "matrix_passes": int(info["matrix_passes"]),
               "passes_per_eigenpair": info["matrix_passes"] / est.k,
               "wall_s": round(wall, 4),
               "eigenvalues": np.asarray(evals).tolist()}
        rec.update(extra or {})
        results["rows"].append(rec)
        row(f"eigsweep/{path}_{est.eigensolver}", wall * 1e6,
            f"passes={rec['matrix_passes']} "
            f"per_pair={rec['passes_per_eigenpair']:.1f}")
        return rec

    def est_for(solver):
        return SpectralClustering(
            k=k, eigensolver=solver, sigma=1.0, lanczos_steps=64,
            block_size=block_size if solver == "block-lanczos" else None)

    # ---- dense in-memory path ------------------------------------------
    # eigh rides along: its matrix_passes (n_pad — the O(n^3)
    # factorization in the iterative solvers' cost unit) makes the rows
    # comparable across ALL registered eigensolvers
    pts, _ = synthetic.blobs(n, k, dim=4, spread=0.8, seed=0)
    mesh = mesh_utils.local_mesh("rows")
    op = AFFINITIES.get("dense")(est_for("lanczos"), jnp.asarray(pts),
                                 jnp.asarray(1.0), mesh)
    dense_recs = {s: solve(est_for(s), op, "dense")
                  for s in (*solvers, "eigh")}

    # ---- out-of-core engine path (budget forces spills) ----------------
    budget = 1 << 19
    reader = BlobChunks(n, k, chunk_size=512, dim=4, spread=0.8, seed=0)
    plan = engine.JobPlan(n=n, chunk_size=512, t=16, k=k, sigma=1.0,
                          memory_budget=budget, seed=0)
    graph, _sig = engine.build_graph(reader, plan)
    op_ooc = engine.make_normalized_operator(graph)
    ooc_recs = {}
    for s in solvers:
        before = dict(graph.store.stats)
        ooc_recs[s] = solve(
            est_for(s), op_ooc, "ooc-topt",
            extra={"store_gets": None})  # filled below
        after = graph.store.stats
        ooc_recs[s]["store_gets"] = after["gets"] - before["gets"]
        ooc_recs[s]["store_loads"] = after["loads"] - before["loads"]
        row(f"eigsweep/ooc_store_{s}", 0.0,
            f"gets={ooc_recs[s]['store_gets']} "
            f"loads={ooc_recs[s]['store_loads']}")

    for path, recs in (("dense", dense_recs), ("ooc", ooc_recs)):
        red = (recs["lanczos"]["matrix_passes"]
               / max(recs["block-lanczos"]["matrix_passes"], 1))
        results[f"{path}_pass_reduction_b{block_size}"] = red
        row(f"eigsweep/{path}_pass_reduction", 0.0,
            f"b={block_size} -> {red:.1f}x fewer passes/eigenpair")
        assert red >= 4, (path, red)
    load_red = (ooc_recs["lanczos"]["store_gets"]
                / max(ooc_recs["block-lanczos"]["store_gets"], 1))
    results["ooc_store_get_reduction"] = load_red
    row("eigsweep/ooc_store_get_reduction", 0.0, f"{load_red:.1f}x")

    # ---- chebdav vs eigh oracle on the paper config --------------------
    from repro.configs import spectral_paper
    kk = spectral_paper.CONFIG.k
    pts_p, _ = synthetic.blobs(600, kk, dim=8, spread=0.6, seed=0)
    xp = jnp.asarray(pts_p)
    base = dict(affinity="triangular", sigma=1.0, seed=0,
                lanczos_steps=spectral_paper.CONFIG.lanczos_steps)
    eigh_est = SpectralClustering(kk, eigensolver="eigh", **base).fit(xp)
    chb_est = SpectralClustering(kk, eigensolver="chebdav", **base).fit(xp)
    a = ari(np.asarray(eigh_est.labels_), np.asarray(chb_est.labels_))
    results["chebdav_vs_eigh_ari"] = float(a)
    row("eigsweep/chebdav_vs_eigh", 0.0,
        f"paper config k={kk} ari={a:.3f} "
        f"passes={chb_est.info_['matrix_passes']}")
    assert a >= 0.95, a

    with open(out_json, "w") as f:
        json.dump(results, f, indent=2)
    print(f"# wrote {out_json}")


def fused_sweep(ns=(1024, 2048, 8192), k: int = 8,
                out_json: str = "BENCH_fused.json"):
    """dense vs fused-rbf vs ooc across an n sweep (paper-config blobs:
    k=8, dim=8, lanczos_steps=64, block width 8).

    Per n: wall seconds, peak affinity-stage bytes (dense: the
    materialized n_pad^2 similarity; fused: points + scale vectors +
    VMEM tiles, as advertised by the operator; ooc: shard-store peak
    RAM), and label agreement — fused vs the dense-path labels at every
    n, both vs the exact eigh labels where eigh is affordable.  The ooc
    rows run under a spill-forcing budget and report the prefetch
    hit/miss counters of the double-buffered shard stream.

    The contract this validates (ISSUE 4 acceptance): at n=8192 the
    fused path matches dense labels at ARI >= 0.99 with <= 10% of the
    dense path's affinity memory.
    """
    from repro import engine
    from repro.cluster import ari
    from repro.data.chunked import ArrayChunks
    from repro.distrib import mesh_utils

    results: dict = {"k": k, "dim": 8, "lanczos_steps": 64, "block_size": 8,
                     "rows": []}

    def fit(affinity, pts, **kw):
        est = SpectralClustering(
            k=k, affinity=affinity, eigensolver="block-lanczos",
            block_size=8, sigma=1.0, seed=0, lanczos_steps=64, **kw)
        t0 = time.perf_counter()
        est.fit(jnp.asarray(pts))
        return est, time.perf_counter() - t0

    mesh = mesh_utils.local_mesh("rows")
    m = mesh_utils.mesh_size(mesh)
    for n in ns:
        pts, _truth = synthetic.blobs(n, k, dim=8, spread=0.6, seed=0)
        n_pad = ((n + m - 1) // m) * m

        dense_est, dense_s = fit("dense", pts)
        dense_labels = np.asarray(dense_est.labels_)
        dense_peak = n_pad * n_pad * 4               # materialized f32 S
        row(f"fused_sweep/dense_n{n}", dense_s * 1e6,
            f"peak_affinity_bytes={dense_peak}")

        fused_est, fused_s = fit("fused-rbf", pts)
        st = fused_est.info_["engine"]
        a_fd = ari(dense_labels, np.asarray(fused_est.labels_))
        row(f"fused_sweep/fused_n{n}", fused_s * 1e6,
            f"peak_affinity_bytes={st['affinity_peak_bytes']} "
            f"({st['affinity_peak_bytes'] / dense_peak:.4f}x dense) "
            f"passes={st['matrix_passes']} "
            f"bytes_streamed={st['bytes_streamed']} ari_vs_dense={a_fd:.3f}")

        rec = {"n": n, "dense_wall_s": round(dense_s, 3),
               "fused_wall_s": round(fused_s, 3),
               "dense_peak_affinity_bytes": dense_peak,
               "fused_peak_affinity_bytes": int(st["affinity_peak_bytes"]),
               "fused_matrix_passes": int(st["matrix_passes"]),
               "fused_bytes_streamed": int(st["bytes_streamed"]),
               "fused_vs_dense_ari": float(a_fd)}

        if n <= 2048:                                # eigh oracle affordable
            eigh_est = SpectralClustering(
                k=k, affinity="dense", eigensolver="eigh", sigma=1.0,
                seed=0).fit(jnp.asarray(pts))
            rec["dense_vs_eigh_ari"] = float(
                ari(np.asarray(eigh_est.labels_), dense_labels))
            rec["fused_vs_eigh_ari"] = float(
                ari(np.asarray(eigh_est.labels_),
                    np.asarray(fused_est.labels_)))
            rec["eigh_matrix_passes"] = int(eigh_est.info_["matrix_passes"])
            row(f"fused_sweep/eigh_n{n}", 0.0,
                f"ari_dense={rec['dense_vs_eigh_ari']:.3f} "
                f"ari_fused={rec['fused_vs_eigh_ari']:.3f}")

        if n <= 2048:                                # the engine sweep rows
            budget = 1 << 18                         # 256 KiB forces spills
            plan = engine.JobPlan(n=n, chunk_size=256, t=16, k=k, sigma=1.0,
                                  memory_budget=budget, lanczos_steps=64,
                                  block_size=8, seed=0, path="ooc")
            t0 = time.perf_counter()
            res = engine.run_job(plan, ArrayChunks(pts.astype(np.float32),
                                                   256))
            ooc_s = time.perf_counter() - t0
            est_stats = res.stats
            a_od = ari(dense_labels, res.labels)
            rec.update(ooc_wall_s=round(ooc_s, 3),
                       ooc_peak_ram_bytes=int(
                           est_stats["store_peak_ram_bytes"]),
                       ooc_bytes_spilled=int(
                           est_stats["store_bytes_spilled"]),
                       ooc_prefetch_hits=int(est_stats["prefetch_hits"]),
                       ooc_prefetch_misses=int(
                           est_stats["prefetch_misses"]),
                       ooc_vs_dense_ari=float(a_od))
            row(f"fused_sweep/ooc_n{n}", ooc_s * 1e6,
                f"peak_ram={rec['ooc_peak_ram_bytes']} "
                f"spilled={rec['ooc_bytes_spilled']} "
                f"prefetch_hits={rec['ooc_prefetch_hits']} "
                f"ari_vs_dense={a_od:.3f}")
            assert est_stats["store_bytes_spilled"] > 0, "budget too lax"

            # same job, RAM-resident store: the readahead is now faster
            # than the consumer, so the hit counter shows the stream
            # staying warm (under the spill budget above the disk stream
            # is producer-bound and hits are rare — that contrast is the
            # point of reporting both)
            plan_ram = engine.JobPlan(n=n, chunk_size=256, t=16, k=k,
                                      sigma=1.0, memory_budget=None,
                                      lanczos_steps=64, block_size=8,
                                      seed=0, path="ooc")
            res_ram = engine.run_job(plan_ram,
                                     ArrayChunks(pts.astype(np.float32),
                                                 256))
            rec.update(
                ooc_ram_prefetch_hits=int(res_ram.stats["prefetch_hits"]),
                ooc_ram_prefetch_misses=int(
                    res_ram.stats["prefetch_misses"]))
            row(f"fused_sweep/ooc_ram_n{n}", 0.0,
                f"prefetch_hits={rec['ooc_ram_prefetch_hits']} "
                f"misses={rec['ooc_ram_prefetch_misses']}")

        results["rows"].append(rec)

    big = results["rows"][-1]
    mem_ratio = (big["fused_peak_affinity_bytes"]
                 / big["dense_peak_affinity_bytes"])
    results["fused_mem_ratio_at_max_n"] = mem_ratio
    row("fused_sweep/acceptance", 0.0,
        f"n={big['n']} ari={big['fused_vs_dense_ari']:.3f} "
        f"mem_ratio={mem_ratio:.4f}")
    assert big["fused_vs_dense_ari"] >= 0.99, big
    assert mem_ratio <= 0.10, mem_ratio
    assert any(r.get("ooc_prefetch_hits", 0)
               + r.get("ooc_ram_prefetch_hits", 0) > 0
               for r in results["rows"]), \
        "engine sweep produced no prefetch hits"

    with open(out_json, "w") as f:
        json.dump(results, f, indent=2)
    print(f"# wrote {out_json}")


def _async_problem(n: int, k: int):
    """The async_sweep problem: n blob points + the spill-forcing plan
    kwargs shared by every run."""
    pts, _ = synthetic.blobs(n, k, dim=4, spread=0.6, seed=0)
    return pts.astype(np.float32), dict(
        n=n, chunk_size=512, t=16, k=k, sigma=1.0, memory_budget=1 << 19,
        lanczos_steps=96, seed=0, path="ooc")


def async_sweep(n: int = 4096, k: int = 3,
                out_json: str = "BENCH_async.json"):
    """The fully-async engine against the same engine at width 1.

    One problem (n=4096 blobs, spill-forcing 512 KiB shard-store budget),
    two runs of the identical math in this process:

      seq        the async engine at width 1 (workers=1, depth=1, sync
                 spills) — the bitwise-parity reference
      async      workers=4, prefetch_depth=4, async spills, single-pass
                 scatter, warm-started eigensolve

    Acceptance (asserted): prefetch hit rate > 0.90; async labels
    BITWISE-identical to seq labels; ooc ARI vs the dense eigh oracle ==
    1.0; and the streaming ooc matmat stays within 2x of the fused
    in-memory matmat at equal n.
    """
    from repro import engine
    from repro.cluster import ari
    from repro.cluster.affinity import AFFINITIES
    from repro.data.chunked import ArrayChunks
    from repro.distrib import mesh_utils

    pts, common = _async_problem(n, k)
    budget = common["memory_budget"]
    results: dict = {"n": n, "k": k, "budget": budget, **common}

    seq_plan = engine.JobPlan(**common, workers=1, prefetch_depth=1,
                              async_spill=False)
    async_plan = engine.JobPlan(**common, workers=4, prefetch_depth=4,
                                async_spill=True)

    # run the width-1 reference first: it also warms every jit the timed
    # async run shares, so the timed wall does not pay compile time
    t0 = time.perf_counter()
    res_seq = engine.run_job(seq_plan, ArrayChunks(pts, 512))
    seq_s = time.perf_counter() - t0
    row("async_sweep/seq_w1", seq_s * 1e6, "async engine at width 1")

    # best of 2 (the seq_w1 run above already compiled everything, so
    # both runs here are warm)
    runs = []
    for _ in range(2):
        t0 = time.perf_counter()
        res_async = engine.run_job(async_plan, ArrayChunks(pts, 512))
        runs.append((time.perf_counter() - t0, res_async))
    async_s, res_async = min(runs, key=lambda r: r[0])
    st = res_async.stats
    hits, misses = st["prefetch_hits"], st["prefetch_misses"]
    hit_rate = hits / max(hits + misses, 1)
    speedup = seq_s / async_s
    row("async_sweep/async_w4", async_s * 1e6,
        f"speedup_vs_w1={speedup:.2f}x hit_rate={hit_rate:.3f} "
        f"overlap_s={st['overlap_s']} build_wall_s={st['build_wall_s']} "
        f"spills={st['store_spills']} spill_joins={st['store_spill_joins']}")
    assert st["store_bytes_spilled"] > 0, "budget was meant to force spills"

    bitwise = bool(np.array_equal(res_seq.labels, res_async.labels))
    row("async_sweep/scheduler_parity", 0.0, f"bitwise_w1={bitwise}")

    # dense eigh oracle on the same points
    eigh_est = SpectralClustering(k=k, affinity="dense", eigensolver="eigh",
                                  sigma=1.0, seed=0).fit(jnp.asarray(pts))
    a_dense = float(ari(np.asarray(eigh_est.labels_), res_async.labels))
    row("async_sweep/ari_vs_dense_oracle", 0.0, f"ari={a_dense:.3f}")

    # streaming matmat vs the fused in-memory matmat at equal n (both
    # through the NormalizedOperator interface, best of 3).  The ooc side
    # times host_matmat — the product the eigensolve actually drives on
    # CPU runtimes; the traced-callback twin is the self-deadlock this PR
    # routed the hot path around, so it must not sit in a benchmark loop.
    graph, _s = engine.build_graph(ArrayChunks(pts, 512), async_plan)
    op_ooc = engine.make_normalized_operator(graph)
    mesh = mesh_utils.local_mesh("rows")
    est = SpectralClustering(k=k, sigma=1.0, seed=0)
    op_fused = AFFINITIES.get("fused-rbf")(est, jnp.asarray(pts),
                                           jnp.asarray(1.0), mesh)
    V = jnp.asarray(np.random.RandomState(0).randn(op_ooc.n_pad, 8),
                    jnp.float32)
    Vh = np.asarray(V)
    ooc_us, _ = _timeit(op_ooc.host_matmat, Vh)
    Vf = V[:op_fused.n_pad] if op_fused.n_pad <= op_ooc.n_pad else \
        jnp.zeros((op_fused.n_pad, 8), jnp.float32).at[:op_ooc.n_pad].set(V)
    fused_us, _ = _timeit(op_fused.matmat, Vf)
    matmat_ratio = ooc_us / fused_us
    row("async_sweep/matmat_ooc_vs_fused", ooc_us,
        f"fused={fused_us:.0f}us ratio={matmat_ratio:.2f}x")
    graph.close()

    results.update(
        seq_wall_s=round(seq_s, 3), async_wall_s=round(async_s, 3),
        speedup_vs_w1=round(speedup, 3),
        prefetch_hits=int(hits), prefetch_misses=int(misses),
        prefetch_hit_rate=round(hit_rate, 4),
        overlap_s=st["overlap_s"], build_wall_s=st["build_wall_s"],
        store_spills=int(st["store_spills"]),
        store_spill_joins=int(st["store_spill_joins"]),
        bytes_spilled=int(st["store_bytes_spilled"]),
        labels_bitwise_identical_w1=bitwise,
        ari_vs_dense_oracle=a_dense,
        matmat_ooc_us=round(ooc_us, 1), matmat_fused_us=round(fused_us, 1),
        matmat_ooc_vs_fused=round(matmat_ratio, 3))

    row("async_sweep/acceptance", 0.0,
        f"speedup_vs_w1={speedup:.2f}x hit_rate={hit_rate:.3f} "
        f"(need >0.90) bitwise={bitwise} ari_dense={a_dense:.3f} "
        f"matmat_ratio={matmat_ratio:.2f}x (need <=2)")
    assert hit_rate > 0.90, hit_rate
    assert bitwise, "workers=4 labels diverged from workers=1"
    assert a_dense == 1.0, a_dense
    assert matmat_ratio <= 2.0, matmat_ratio

    with open(out_json, "w") as f:
        json.dump(results, f, indent=2)
    print(f"# wrote {out_json}")


def serve_sweep(n: int = 8192, k: int = 8, ms=(1024, 8192),
                out_json: str = "BENCH_serve.json"):
    """The serving path (ISSUE 5 acceptance): fused vs dense out-of-sample
    transform at m queries against an n=8192-point fitted model.

    Per m: wall seconds and peak transform-stage bytes for both paths
    (dense: the materialized (m, n) query-vs-train kernel; fused: the
    O((m+n)*d + n*k) working set the serving layer advertises), plus
    predict-label parity.  Then the persistence contract — save -> load ->
    predict must be bitwise-equal to the fitted estimator — and the
    batched predict service's throughput/latency on the loaded model.

    Acceptance gates asserted here: fused peak <= 5% of dense at m=n=8192
    with label parity, and the round-trip bitwise equality.
    """
    import os
    import tempfile

    from repro.cluster import serving
    from repro.launch.cluster_serve import (ClusterServer, PredictRequest,
                                            summarize)

    results: dict = {"n": n, "k": k, "dim": 8, "rows": []}
    pts, _ = synthetic.blobs(n, k, dim=8, spread=0.6, seed=0)
    est = SpectralClustering(k=k, affinity="fused-rbf",
                             eigensolver="block-lanczos", block_size=8,
                             sigma=1.0, seed=0, lanczos_steps=64)
    t0 = time.perf_counter()
    est.fit(jnp.asarray(pts))
    fit_s = time.perf_counter() - t0
    results["fit_wall_s"] = round(fit_s, 3)
    row("serve_sweep/fit", fit_s * 1e6, f"n={n} affinity=fused-rbf")

    rng = np.random.RandomState(1)
    for m in ms:
        idx = rng.choice(n, size=m)
        q = jnp.asarray((pts[idx] + 0.05 * rng.randn(m, pts.shape[1])
                         ).astype(np.float32))

        def timed_labels(path):
            est.transform_path = path
            jax.block_until_ready(est.predict(q))        # warm/compile
            t0 = time.perf_counter()
            labels = jax.block_until_ready(est.predict(q))
            return np.asarray(labels), time.perf_counter() - t0

        dense_labels, dense_s = timed_labels("dense")
        dense_peak = m * n * 4                           # the (m, n) kernel
        row(f"serve_sweep/dense_m{m}", dense_s * 1e6,
            f"peak_transform_bytes={dense_peak}")

        fused_labels, fused_s = timed_labels("fused")
        fused_peak = serving.transform_peak_bytes(m, n, pts.shape[1], k)
        a = ari(dense_labels, fused_labels)
        exact = float(np.mean(dense_labels == fused_labels))
        row(f"serve_sweep/fused_m{m}", fused_s * 1e6,
            f"peak_transform_bytes={fused_peak} "
            f"({fused_peak / dense_peak:.4f}x dense) "
            f"ari_vs_dense={a:.3f} label_match={exact:.4f}")
        results["rows"].append({
            "m": m, "dense_wall_s": round(dense_s, 4),
            "fused_wall_s": round(fused_s, 4),
            "dense_peak_transform_bytes": dense_peak,
            "fused_peak_transform_bytes": int(fused_peak),
            "fused_vs_dense_ari": float(a),
            "fused_vs_dense_label_match": exact,
        })

    big = results["rows"][-1]
    mem_ratio = (big["fused_peak_transform_bytes"]
                 / big["dense_peak_transform_bytes"])
    results["fused_mem_ratio_at_max_m"] = mem_ratio
    row("serve_sweep/acceptance", 0.0,
        f"m={big['m']} mem_ratio={mem_ratio:.4f} "
        f"ari={big['fused_vs_dense_ari']:.3f}")
    assert mem_ratio <= 0.05, mem_ratio
    assert big["fused_vs_dense_ari"] >= 0.99, big

    # -- persistence round trip: bitwise predict parity -------------------
    est.transform_path = "auto"
    with tempfile.TemporaryDirectory() as d:
        model_dir = os.path.join(d, "model")
        t0 = time.perf_counter()
        est.save(model_dir)
        save_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        est2 = SpectralClustering.load(model_dir)
        load_s = time.perf_counter() - t0
        q = jnp.asarray((pts[:2048] + 0.05).astype(np.float32))
        p1 = np.asarray(est.predict(q))
        p2 = np.asarray(est2.predict(q))
        bitwise = bool((p1 == p2).all())
        e1 = np.asarray(est.transform(q))
        e2 = np.asarray(est2.transform(q))
        bitwise = bitwise and bool((e1 == e2).all())
        results["save_wall_s"] = round(save_s, 3)
        results["load_wall_s"] = round(load_s, 3)
        results["roundtrip_predict_bitwise_equal"] = bitwise
        row("serve_sweep/roundtrip", (save_s + load_s) * 1e6,
            f"save={save_s:.2f}s load={load_s:.2f}s bitwise={bitwise}")
        assert bitwise

        # -- batched predict service on the loaded model ------------------
        est2.transform_path = "fused"
        queue = []
        for rid in range(16):
            mi = 512 + rng.randint(-64, 65)
            idx = rng.choice(n, size=mi)
            queue.append(PredictRequest(
                rid=rid, points=(pts[idx]
                                 + 0.05 * rng.randn(mi, pts.shape[1])
                                 ).astype(np.float32)))
        srv = ClusterServer(est2, batch_rows=1024)
        t0 = time.perf_counter()
        done = srv.run(queue)
        wall = time.perf_counter() - t0
        s = summarize(done, wall)
        fill = srv.stats["rows_live"] / max(
            srv.stats["rows_live"] + srv.stats["rows_padded"], 1)
        results["service"] = {
            "batch_rows": 1024, **{k2: (round(v, 2) if isinstance(v, float)
                                        else v) for k2, v in s.items()},
            "batch_steps": srv.steps, "fill": round(fill, 3),
        }
        row("serve_sweep/service", wall * 1e6,
            f"{s['points']} pts in {srv.steps} steps "
            f"{s['points_per_s']:.0f} pts/s fill={fill:.0%} "
            f"p50={s['latency_p50_ms']:.0f}ms")
        assert all(r.done for r in done)

    with open(out_json, "w") as f:
        json.dump(results, f, indent=2)
    print(f"# wrote {out_json}")


def chaos_sweep(n: int = 4096, k: int = 3,
                out_json: str = "BENCH_chaos.json"):
    """Hadoop-grade fault tolerance (ISSUE 9 acceptance) in three acts.

    (a) Recovery is invisible: the n=4096 out-of-core job is run clean,
        then under injected map/shuffle/reduce task failures — including
        MID-fold failures, where the dying attempt has already consumed
        part of its input set and the retry must re-materialize the
        missing blocks from lineage — then with
        spilled CSR shards corrupted on disk (bitflip + truncate), then
        with a 3 s map straggler under speculative re-execution — every
        faulted run must produce labels BITWISE-equal to the clean run
        (so ARI == 1 by construction, and it is still asserted).
    (b) Resilience is ~free: best-of-3 graph builds with the retry
        machinery at its defaults vs max_retries=0 — <= 3% overhead.
    (c) Overload degrades, not collapses: the batched predict service
        under 2x its admission bound sheds the excess with typed
        rejections while the admitted requests' p99 stays <= 2x the
        unloaded p99.
    """
    from repro import engine
    from repro.data.chunked import ArrayChunks
    from repro.launch.cluster_serve import (ClusterServer, PredictRequest,
                                            summarize)

    pts, common = _async_problem(n, k)
    results: dict = {"n": n, "k": k, "budget": common["memory_budget"],
                     "runs": {}}

    def run_engine(faults=None, **kw):
        plan = engine.JobPlan(**common, workers=4, prefetch_depth=4,
                              faults=faults, **kw)
        t0 = time.perf_counter()
        res = engine.run_job(plan, ArrayChunks(pts, 512))
        return res, time.perf_counter() - t0

    # -- (a) fault injection: bitwise recovery ----------------------------
    run_engine()                                      # warm every jit
    res_clean, clean_s = run_engine()
    row("chaos_sweep/clean", clean_s * 1e6,
        f"spills={res_clean.stats['store_spills']}")
    results["runs"]["clean"] = {"wall_s": round(clean_s, 3)}

    fault_runs = {
        "task_failures": dict(
            faults=(engine.FaultPlan()
                    .fail("map", (0, 1))
                    .fail_n("map", (2, 3), 2)
                    .fail("shuffle", 1)
                    .fail_midfold("shuffle", 2, after_inputs=3)
                    .fail("reduce", 0)
                    .fail_midfold("reduce", 3, after_inputs=2)),
            kw=dict(retry_backoff_s=0.01)),
        "spill_corruption": dict(
            faults=(engine.FaultPlan()
                    .corrupt("shard/0", "bitflip")
                    .corrupt("shard/3", "truncate")),
            kw={}),
        "straggler": dict(
            faults=engine.FaultPlan().delay("map", (1, 1), 3.0),
            kw=dict(speculation_factor=3.0)),
    }
    for tag, cfg in fault_runs.items():
        faults = cfg["faults"]
        res, wall = run_engine(faults=faults, **cfg["kw"])
        st = res.stats
        bitwise = bool(np.array_equal(res_clean.labels, res.labels))
        a = float(ari(res_clean.labels, res.labels))
        detail = (f"bitwise={bitwise} ari={a:.3f} "
                  f"retries={st['retries']} "
                  f"healed={st['inputs_healed']} "
                  f"recoveries={st['store_recoveries']} "
                  f"spec_launched={st['speculative_launched']} "
                  f"spec_won={st['speculative_won']} fired={faults.fired}")
        row(f"chaos_sweep/{tag}", wall * 1e6, detail)
        results["runs"][tag] = {
            "wall_s": round(wall, 3), "bitwise_equal_labels": bitwise,
            "ari_vs_clean": a, "retries": int(st["retries"]),
            "task_failures": int(st["task_failures"]),
            "inputs_healed": int(st["inputs_healed"]),
            "store_recoveries": int(st["store_recoveries"]),
            "speculative_launched": int(st["speculative_launched"]),
            "speculative_won": int(st["speculative_won"]),
            "faults_fired": dict(faults.fired),
        }
        assert bitwise, f"{tag}: labels diverged from the fault-free run"
        assert a == 1.0, (tag, a)
    assert results["runs"]["task_failures"]["retries"] >= 6
    # shuffle 2 consumed 3 cand blocks, reduce 3 consumed topt + 1 mirror
    assert results["runs"]["task_failures"]["inputs_healed"] >= 5
    assert results["runs"]["spill_corruption"]["store_recoveries"] >= 1
    assert results["runs"]["straggler"]["speculative_won"] >= 1

    # -- (b) zero-fault overhead of the resilience machinery --------------
    def best_build(**kw):
        walls = []
        for _ in range(3):
            plan = engine.JobPlan(**common, workers=4, prefetch_depth=4,
                                  **kw)
            t0 = time.perf_counter()
            graph, _sig = engine.build_graph(ArrayChunks(pts, 512), plan,
                                             prewarm=False)
            walls.append(time.perf_counter() - t0)
            graph.close()
        return min(walls)

    base_s = best_build(max_retries=0)
    resil_s = best_build()                 # defaults: max_retries=2
    overhead = resil_s / base_s - 1.0
    row("chaos_sweep/overhead", resil_s * 1e6,
        f"base={base_s:.3f}s resilient={resil_s:.3f}s "
        f"overhead={overhead:.2%} (need <=3%)")
    results["overhead"] = {
        "build_wall_s_no_retry": round(base_s, 4),
        "build_wall_s_resilient": round(resil_s, 4),
        "overhead_frac": round(overhead, 4),
    }
    assert overhead <= 0.03, f"resilience overhead {overhead:.2%} > 3%"

    # -- (c) serve under 2x overload: typed shed, bounded p99 -------------
    serve_n, m, n_req = 2048, 256, 8
    spts, _ = synthetic.blobs(serve_n, k, dim=8, spread=0.6, seed=0)
    est = SpectralClustering(k=k, affinity="fused-rbf", sigma=1.0,
                             seed=0, lanczos_steps=48)
    est.fit(jnp.asarray(spts))
    rng = np.random.RandomState(2)

    def make_queue(count):
        return [PredictRequest(
            rid=rid,
            points=(spts[rng.choice(serve_n, size=m)]
                    + 0.05 * rng.randn(m, spts.shape[1])
                    ).astype(np.float32)) for rid in range(count)]

    np.asarray(est.predict(jnp.asarray(spts[:256])))  # warm the compile
    bound = n_req * m                                 # rows of capacity

    srv_u = ClusterServer(est, batch_rows=256)
    t0 = time.perf_counter()
    done_u = srv_u.run(make_queue(n_req))             # offered = capacity
    s_u = summarize(done_u, time.perf_counter() - t0)

    srv_o = ClusterServer(est, batch_rows=256, max_pending_rows=bound)
    t0 = time.perf_counter()
    done_o = srv_o.run(make_queue(2 * n_req))         # offered = 2x
    s_o = summarize(done_o, time.perf_counter() - t0)

    shed = [r for r in done_o if r.status == "shed"]
    p99_ratio = s_o["latency_p99_ms"] / max(s_u["latency_p99_ms"], 1e-9)
    row("chaos_sweep/serve_overload", 0.0,
        f"unloaded_p99={s_u['latency_p99_ms']:.0f}ms "
        f"overload_p99={s_o['latency_p99_ms']:.0f}ms "
        f"ratio={p99_ratio:.2f}x (need <=2) shed={len(shed)}")
    results["serve"] = {
        "batch_rows": 256, "rows_per_request": m,
        "max_pending_rows": bound,
        "offered_requests_unloaded": n_req,
        "offered_requests_overload": 2 * n_req,
        "unloaded_p99_ms": s_u["latency_p99_ms"],
        "overload_admitted_p99_ms": s_o["latency_p99_ms"],
        "p99_ratio": round(p99_ratio, 3),
        "completed": s_o["completed"], "shed": s_o["shed"],
        "expired": s_o["expired"],
    }
    assert all(r.done for r in done_u)
    assert shed, "2x overload against a bounded queue must shed"
    assert all(r.error and "shed" in r.error for r in shed)
    assert s_o["completed"] >= 1
    assert p99_ratio <= 2.0, p99_ratio

    with open(out_json, "w") as f:
        json.dump(results, f, indent=2)
    print(f"# wrote {out_json}")


def tune_sweep(ns=(1024, 4096), quick: bool = False,
               out_json: str = "BENCH_tune.json"):
    """The schedule autotuner sweep (repro.tune): every schedulable Pallas
    kernel at n in ``ns``, default schedule vs best-of-candidates wall
    time, winners persisted in the schedule cache (REPRO_SCHEDULE_CACHE or
    ~/.cache/repro/schedules.json).  Re-running prints cache_hit=True rows
    and does no timing — delete the cache file to retune.  ``--quick``
    shrinks n and the candidate grid for the CI smoke job.

    The default schedule is always among the candidates, so tuned wall is
    <= default wall on every kernel by construction (asserted here).
    """
    from repro import tune

    cache = tune.default_cache()
    if quick:
        ns = (256,)
    reports = tune.tune_all(ns, cache=cache, quick=quick,
                            log=lambda msg: print(f"# {msg}", flush=True))
    results = {"device": tune.device_kind(), "cache_path": cache.path,
               "quick": quick, "rows": reports}
    for rep in reports:
        name = f"tune_sweep/{rep['kernel']}_n{rep['shape']['n']}"
        if rep["cache_hit"]:
            row(name, float(rep.get("best_us") or 0.0),
                f"cache_hit=True schedule={rep['best']}")
            continue
        row(name, rep["best_us"],
            f"cache_hit=False default_us={rep['default_us']} "
            f"speedup={rep['speedup']}x schedule={rep['best']}")
        assert rep["best_us"] <= rep["default_us"] + 1e-9, rep
    with open(out_json, "w") as f:
        json.dump(results, f, indent=2)
    print(f"# wrote {out_json} (cache: {cache.path})")


MODES = {
    "table1_phases": table1_phases,
    "fig5_speedup": fig5_speedup,
    "rings_quality": rings_quality,
    "lanczos_residual": lanczos_residual,
    "assigner_backends": assigner_backends,
    "kernels": kernels,
    "engine_ooc": engine_ooc,
    "eigensolver_sweep": eigensolver_sweep,
    "fused_sweep": fused_sweep,
    "async_sweep": async_sweep,
    "serve_sweep": serve_sweep,
    "tune_sweep": tune_sweep,
    "chaos_sweep": chaos_sweep,
}

# modes the bare invocation runs (the sweep is opt-in: it is a benchmark
# of its own with a JSON artifact)
DEFAULT_MODES = ("table1_phases", "fig5_speedup", "rings_quality",
                 "lanczos_residual", "assigner_backends", "kernels",
                 "engine_ooc")


def main(argv=None) -> None:
    if argv is None:
        import sys
        argv = sys.argv[1:]
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("modes", nargs="*", choices=[[], *MODES],
                    help="benchmark modes to run (default: full suite "
                         "minus eigensolver_sweep)")
    ap.add_argument("--quick", action="store_true",
                    help="tune_sweep only: small n + reduced candidate "
                         "grid (the CI autotune smoke configuration)")
    args = ap.parse_args(argv)
    compile_cache.enable()
    print("name,us_per_call,derived")
    for mode in (args.modes or DEFAULT_MODES):
        if mode == "tune_sweep":
            tune_sweep(quick=args.quick)
        else:
            MODES[mode]()
    print(f"# {len(ROWS)} rows")


if __name__ == "__main__":
    main()
