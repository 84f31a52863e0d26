"""Driver for the paper's pipeline: cluster points or a topology graph
file on all local devices, with phase checkpointing.

Each pipeline phase is a registry-selected backend of
:class:`repro.cluster.SpectralClustering`:

    PYTHONPATH=src python -m repro.launch.spectral_job --blobs 600 --k 3
    PYTHONPATH=src python -m repro.launch.spectral_job --rings 512 --k 2 \\
        --affinity compact --eigensolver lanczos --assigner minibatch
    PYTHONPATH=src python -m repro.launch.spectral_job --graph topo.txt --k 8
    PYTHONPATH=src python -m repro.launch.spectral_job --points emb.npy \\
        --k 50 --affinity fused-rbf --eigensolver block-lanczos \\
        --block-size 64 --lanczos-steps 320
    PYTHONPATH=src python -m repro.launch.spectral_job --blobs 4096 --k 3 \\
        --engine mapreduce --chunk-size 512 --memory-budget 1048576
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache, obs
from repro.checkpoint import CheckpointManager
from repro.cluster import AFFINITIES, ASSIGNERS, EIGENSOLVERS, SpectralClustering
from repro.data import graph_file, points_file, synthetic
from repro.distrib import mesh_utils


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--blobs", type=int, default=0, help="n points in k blobs")
    ap.add_argument("--rings", type=int, default=0, help="n points in k rings")
    ap.add_argument("--graph", default=None, help="paper §5.1 topology file")
    ap.add_argument("--points", default=None, metavar="PATH.npy",
                    help="(n, d) rows to cluster: float32, or bfloat16 "
                         "(ml_dtypes), which --affinity fused-rbf keeps "
                         "bfloat16 on the device")
    ap.add_argument("--k", type=int, default=3)
    ap.add_argument("--affinity", default="triangular",
                    choices=AFFINITIES.names(),
                    help="phase-1 backend (forced to 'graph' by --graph)")
    ap.add_argument("--eigensolver", default="lanczos",
                    choices=EIGENSOLVERS.names(), help="phase-2 backend")
    ap.add_argument("--assigner", default="lloyd", choices=ASSIGNERS.names(),
                    help="phase-3 backend")
    ap.add_argument("--mode", default=None, choices=["triangular", "full"],
                    help="deprecated alias: triangular/full -> "
                         "--affinity triangular/dense")
    ap.add_argument("--sparsify-t", type=int, default=None,
                    help="top-t per row for --affinity knn-topt / ooc-topt")
    ap.add_argument("--compute-dtype", default=None,
                    choices=["float32", "f32", "bfloat16", "bf16"],
                    help="MXU product precision inside --affinity fused-rbf "
                         "(accumulation is always f32)")
    ap.add_argument("--schedule", default=None,
                    help="kernel schedule for the Pallas-backed paths: "
                         "'default' (built-in tiles), 'auto' (persistent "
                         "schedule cache, see repro.tune), or an inline "
                         "JSON object of Schedule fields, e.g. "
                         "'{\"bm\": 256, \"bn\": 256}'")
    ap.add_argument("--engine", default=None, choices=["mapreduce"],
                    help="run phase 1 out-of-core through repro.engine "
                         "(forces --affinity ooc-topt)")
    ap.add_argument("--chunk-size", type=int, default=1024,
                    help="rows per engine chunk (--engine mapreduce)")
    ap.add_argument("--memory-budget", type=int, default=None,
                    help="engine shard-store RAM budget in bytes; shards "
                         "beyond it spill to --spill-dir")
    ap.add_argument("--spill-dir", default=None,
                    help="engine spill directory (default: temp dir)")
    ap.add_argument("--workers", type=int, default=1,
                    help="engine task-pool width: map/shuffle/reduce run "
                         "dependency-driven on this many threads (results "
                         "are bitwise-identical at any width)")
    ap.add_argument("--prefetch-depth", type=int, default=2,
                    help="engine shard readahead window: how many upcoming "
                         "CSR shards the streaming matmat fetches "
                         "concurrently")
    ap.add_argument("--max-retries", type=int, default=2,
                    help="engine per-task retry budget before the build "
                         "aborts (Hadoop-style task attempts)")
    ap.add_argument("--speculation-factor", type=float, default=0.0,
                    help="launch a speculative backup attempt once a task "
                         "runs this many times longer than the running "
                         "median (0 disables; first completion wins)")
    ap.add_argument("--stage-timeout-s", type=float, default=None,
                    help="engine per-stage wall-clock deadline; on expiry "
                         "the build raises EngineTimeoutError and the "
                         "affinity falls back to the in-memory knn-topt path")
    ap.add_argument("--chaos", default=None, metavar="JSON",
                    help="deterministic fault-injection plan for resilience "
                         "drills, e.g. '{\"fail\": [[\"map\", \"0-0\", 0]], "
                         "\"corrupt\": {\"shard/0\": \"bitflip\"}}' "
                         "(see repro.engine.FaultPlan.from_spec)")
    ap.add_argument("--lanczos-steps", type=int, default=48,
                    help="target Krylov dimension (block solvers run "
                         "ceil(steps / block-size) block steps)")
    ap.add_argument("--block-size", type=int, default=None,
                    help="eigensolve block width b for --eigensolver "
                         "block-lanczos / chebdav (each matrix pass is "
                         "amortized over b vectors)")
    ap.add_argument("--cheb-degree", type=int, default=12,
                    help="Chebyshev filter degree (--eigensolver chebdav)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--trace-out", default=None, metavar="FILE.json",
                    help="write a Chrome-trace of the run (chrome://tracing)")
    ap.add_argument("--metrics-out", default=None, metavar="FILE.json",
                    help="write the metrics registry snapshot as JSON")
    args = ap.parse_args(argv)
    if args.graph and args.points:
        ap.error("--graph and --points are two inputs; give one")
    if (args.eigensolver in ("lanczos", "block-lanczos")
            and args.lanczos_steps < args.k):
        ap.error(f"--lanczos-steps {args.lanczos_steps} is below --k "
                 f"{args.k}: the Krylov space must hold k Ritz vectors")
    compile_cache.enable()

    affinity = args.affinity
    if args.mode is not None:
        affinity = {"triangular": "triangular", "full": "dense"}[args.mode]
    if args.engine:
        if args.graph:
            ap.error("--engine applies to point datasets; --graph feeds the "
                     "graph affinity directly")
        affinity = "ooc-topt"

    schedule = args.schedule
    if isinstance(schedule, str) and schedule.lstrip().startswith("{"):
        import json
        schedule = json.loads(schedule)   # inline Schedule-field object

    faults = None
    if args.chaos:
        from repro import engine
        faults = engine.FaultPlan.from_spec(args.chaos)

    mesh = mesh_utils.local_mesh("rows")
    mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    est = SpectralClustering(
        k=args.k, affinity="graph" if args.graph else affinity,
        eigensolver=args.eigensolver, assigner=args.assigner,
        lanczos_steps=args.lanczos_steps, block_size=args.block_size,
        cheb_degree=args.cheb_degree, sparsify_t=args.sparsify_t,
        compute_dtype=args.compute_dtype, schedule=schedule,
        chunk_size=args.chunk_size,
        memory_budget=args.memory_budget, spill_dir=args.spill_dir,
        workers=args.workers, prefetch_depth=args.prefetch_depth,
        max_retries=args.max_retries,
        speculation_factor=args.speculation_factor,
        stage_timeout_s=args.stage_timeout_s, faults=faults,
        mesh=mesh)

    t0 = time.perf_counter()
    # the job from input to labels on the host; the estimator's ``fit``
    # span nests under it, beside the input phases
    with obs.span("job"):
        if args.graph:
            with obs.span("job.parse"):
                n, edges = graph_file.parse_topology(args.graph)
            with obs.span("job.adjacency") as sp:
                adj = graph_file.adjacency_sparse(n, edges)
                sp.set(nnz=adj.nnz, nnz_padded=adj.weights.size)
            with obs.span("job.to_device"):
                adj = jax.block_until_ready(jax.device_put(adj))
            est.fit_graph(adj, checkpointer=mgr)
            truth = None
        elif args.points:
            with obs.span("job.load"):
                pts = points_file.load_points(args.points)
            with obs.span("job.to_device"):
                x = jax.block_until_ready(jnp.asarray(pts))
            est.fit(x, checkpointer=mgr)
            truth = None
        else:
            with obs.span("job.data"):
                if args.rings:
                    pts, truth = synthetic.rings(args.rings, args.k)
                else:
                    pts, truth = synthetic.blobs(args.blobs or 600, args.k)
                x = jax.block_until_ready(jnp.asarray(pts))
            est.fit(x, checkpointer=mgr)
        labels = np.asarray(est.labels_)
    dt = time.perf_counter() - t0

    sizes = np.bincount(labels, minlength=args.k)
    print(f"[spectral] n={len(labels)} k={args.k} "
          f"affinity={est.info_['affinity']} eigensolver={est.eigensolver} "
          f"assigner={est.assigner} devices={mesh_utils.mesh_size(mesh)} "
          f"time={dt:.2f}s")
    print(f"[spectral] eigenvalues: {np.asarray(est.eigenvalues_)}")
    if "matrix_passes" in est.info_:
        print(f"[spectral] matrix_passes={est.info_['matrix_passes']}")
    print(f"[spectral] cluster sizes: {sizes}")
    eng = est.info_.get("engine")
    if eng and "map_tasks" in eng:
        print(f"[engine] map={eng['map_tasks']} shuffle={eng['shuffle_tasks']} "
              f"reduce={eng['reduce_tasks']} chunks={eng['chunks']} "
              f"nnz={eng['nnz']}")
        print(f"[engine] spilled_shards={eng['spilled_shards']} "
              f"spills={eng['store_spills']} "
              f"bytes_spilled={eng['store_bytes_spilled']} "
              f"peak_ram={eng['store_peak_ram_bytes']}")
        if "prefetch_hits" in eng:
            print(f"[engine] prefetch_hits={eng['prefetch_hits']} "
                  f"prefetch_misses={eng['prefetch_misses']}")
        if "overlap_s" in eng:
            print(f"[engine] workers={eng['workers']} "
                  f"build_wall_s={eng['build_wall_s']} "
                  f"overlap_s={eng['overlap_s']} "
                  f"spill_joins={eng['store_spill_joins']}")
        print(f"[obs] engine.retries={eng.get('retries', 0)} "
              f"engine.task_failures={eng.get('task_failures', 0)} "
              f"engine.shard_recovered={eng.get('store_recoveries', 0)} "
              f"engine.speculative_launched="
              f"{eng.get('speculative_launched', 0)} "
              f"engine.speculative_won={eng.get('speculative_won', 0)}")
    if "affinity_fallback" in est.info_:
        print(f"[engine] fallback: {est.info_['affinity_fallback']}")
    elif eng and "bytes_streamed" in eng:  # the fused matrix-free affinity
        print(f"[fused] rows={eng['row_dtype']} "
              f"compute_dtype={eng['compute_dtype']} "
              f"passes={eng['matrix_passes']} "
              f"bytes_streamed={eng['bytes_streamed']} "
              f"peak_affinity_bytes={eng['affinity_peak_bytes']} "
              f"(dense equiv {eng['dense_equiv_bytes']})")
    sched_info = est.info_.get("schedule")
    if sched_info:
        print(f"[schedule] source={sched_info['source']} "
              f"value={sched_info['value']}")
    if "obs" in est.info_:
        print(obs.phase_summary(est.info_["obs"]))
    obs.write_artifacts(args.trace_out, args.metrics_out)
    if truth is not None:
        from itertools import permutations
        k = args.k
        if k <= 6:
            acc = max(np.mean(np.array([p[t] for t in truth]) == labels)
                      for p in permutations(range(k)))
            print(f"[spectral] accuracy vs planted labels: {acc:.3f}")
    return est


if __name__ == "__main__":
    main()
