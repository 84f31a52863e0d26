#!/usr/bin/env python3
"""Run the fit job and the predict service once on a TPU, at real sizes.

    python chip_smoke.py             # one chip: the four phases below
    python chip_smoke.py --chips 4   # four chips: sharded fits vs one device

One chip, in one process and in this order:

  fused   SpectralClustering(k=8, affinity="fused-rbf",
          eigensolver="block-lanczos") on n=262,144 seeded blobs (d=8):
          ARI >= 0.99 against the planted labels.  A 4,096-point
          subsample fit must reach ARI >= 0.99 against the float64 NumPy
          reference (repro.cluster.reference) on the same points.
  graph   spectral_job --graph on a seeded planted-partition topology of
          the paper's size (10,029 vertices, 21,054 edges): ARI against
          the planted blocks no more than 0.02 below the same command's
          ARI on the CPU (GRAPH_ARI_CPU).  The witness is float64 NumPy
          on the host: the graph has more connected components than k,
          so its k smallest eigenvalues are exactly 0, and 48 Lanczos
          steps resolve only the first of them.  The first must be 0
          within 1e-5, none may be below -1e-5, and the unconverged rest
          must lie within GRAPH_RITZ_RTOL of the same 48-step recurrence
          in float64 (repro.cluster.reference).
  ooc     spectral_job --engine mapreduce at n=65,536, k=8, chunks of
          4,096 rows, a top-50 graph and block Lanczos, under a memory
          budget that forces spills (the device CSR product): ARI >= 0.99,
          spilled shards > 0, no task retries.
  serve   the fused model saved, loaded and served by ClusterServer
          (fused transform, 1,024-row batches) on 64 requests of 100 to
          1,000 rows: every request completes, with the labels est.predict
          gives for the same rows.

Four chips (--chips 4), and nothing else: the fused-rbf fit at n=262,144
row-sharded over a 4-device mesh against the same fit on one device, and
the triangular fit (spectral_job's default affinity) at n=16,384 on 4
devices against 1.  Label ARI >= 0.999, and the k smallest eigenvalues
within 1e-4 (relative, with |lambda| floored at 1).

Each phase prints its wall time, first-call (compile) against warm time,
ARI, matrix passes and its [obs] summary; times are one smoke run, not a
benchmark.  The script fails before any work when JAX finds no TPU, and
fails on any fallback (``info_["affinity_fallback"]``,
``engine.path_fallbacks``), on any kernel schedule resolved to interpret
mode, and on any failed check.  The last line of stdout is one JSON
object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

K = 8
N_FIT = 262_144          # configs/spectral_paper.PRODUCTION_N
N_SUB = 4_096
N_OOC = 65_536
OOC_CHUNK, OOC_BUDGET = 4_096, 4 << 20
# the top-t graph of spectral_job's well-separated blobs has k components
# (a k-fold zero eigenvalue, which single-vector Lanczos cannot resolve)
# and a small gap inside each: block Lanczos over a t=50 graph converges
# in 100 block steps (ARI 1.0 on the CPU; t=10 needs more than 100)
OOC_SPARSIFY_T, OOC_LANCZOS_STEPS = 50, 800
N_TRI = 16_384
GRAPH_N, GRAPH_EDGES = 10_029, 21_054
# ARI of `spectral_job --graph <topology> --k 8` against the planted blocks
# on the CPU (JAX_PLATFORMS=cpu), same seeded topology as the graph phase
GRAPH_ARI_CPU = 0.520773777462672
# relative distance of the unconverged Ritz values from the float64
# recurrence: float32 rounding alone puts them 0.082 away on the CPU, a
# bfloat16 matrix pass about 1.0 away (the Ritz values fall to 0)
GRAPH_RITZ_RTOL = 0.15
SERVE_REQUESTS, SERVE_BATCH = 64, 1024


class CheckFailed(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    print(f"  check {'ok  ' if ok else 'FAIL'} {what}", flush=True)
    if not ok:
        raise CheckFailed(what)


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def no_fallback(est=None) -> None:
    """No degraded path ran: no affinity fallback, no rerouted engine job,
    no kernel schedule resolved to the Pallas interpreter."""
    from repro import obs
    if est is not None:
        check("affinity_fallback" not in est.info_,
              f"no affinity fallback ({est.info_.get('affinity_fallback')})")
    snap = obs.snapshot()
    reroutes = snap.get("engine.path_fallbacks", {}).get("value", 0)
    check(reroutes == 0, f"engine.path_fallbacks == 0 (got {reroutes})")
    interp = {k: v["value"] for k, v in snap.items()
              if k.startswith("tune.resolved") and "interpret=True" in k
              and v["value"]}
    check(not interp, f"no schedule resolved to interpret mode {interp}")


def blobs(n: int):
    from repro.data import synthetic
    return synthetic.blobs(n, K, dim=8, spread=0.6, seed=0)


def fused_estimator(mesh=None):
    from repro.cluster import SpectralClustering
    return SpectralClustering(k=K, affinity="fused-rbf",
                              eigensolver="block-lanczos", mesh=mesh)


def report_fit(tag: str, est, cold_s: float, warm_s: float | None) -> None:
    from repro import obs
    info = est.info_
    passes = (info.get("engine") or {}).get("matrix_passes",
                                            info.get("matrix_passes"))
    warm = "" if warm_s is None else f" warm={warm_s:.3f}s"
    print(f"  {tag}: first_call={cold_s:.3f}s{warm} matrix_passes={passes} "
          f"schedule={info.get('schedule')}")
    print(f"  eigenvalues={np.asarray(est.eigenvalues_).tolist()}")
    if "obs" in info:
        print("  " + obs.phase_summary(info["obs"]))


# -- one chip ----------------------------------------------------------------

def phase_fused(state: dict) -> None:
    import jax.numpy as jnp

    from repro.cluster import ari
    from repro.cluster.reference import spectral_reference

    x, truth = blobs(N_FIT)
    _, cold = timed(lambda: fused_estimator().fit(jnp.asarray(x)))
    est, warm = timed(lambda: fused_estimator().fit(jnp.asarray(x)))
    report_fit(f"fused-rbf n={N_FIT}", est, cold, warm)
    a = ari(truth, np.asarray(est.labels_))
    print(f"  ARI vs planted labels = {a!r}")
    check(a >= 0.99, "fused fit ARI >= 0.99 against the planted labels")
    check(est.info_["schedule"]["value"]["interpret"] is False,
          "fused kernel compiled, not interpreted")
    no_fallback(est)
    state["est"], state["x"] = est, x

    idx = np.random.RandomState(1).choice(N_FIT, N_SUB, replace=False)
    sub = x[idx]
    est_sub, t_sub = timed(lambda: fused_estimator().fit(jnp.asarray(sub)))
    (ref_labels, ref_evals), t_ref = timed(
        lambda: spectral_reference(sub, K, float(est_sub.sigma_)))
    a_ref = ari(ref_labels, np.asarray(est_sub.labels_))
    d_ev = np.max(np.abs(np.asarray(est_sub.eigenvalues_, np.float64)
                         - ref_evals))
    print(f"  subsample n={N_SUB}: fit={t_sub:.3f}s reference={t_ref:.3f}s "
          f"ARI vs float64 reference = {a_ref!r} max|d eigenvalue|={d_ev:.3e}")
    check(a_ref >= 0.99, "subsample ARI >= 0.99 against the NumPy reference")
    no_fallback(est_sub)


def phase_graph(state: dict) -> None:
    import jax
    import jax.numpy as jnp

    from repro.cluster import ari
    from repro.cluster.reference import (graph_components,
                                         graph_lanczos_reference)
    from repro.data import graph_file, synthetic
    from repro.launch import spectral_job

    edges, truth = synthetic.synthetic_graph(GRAPH_N, GRAPH_EDGES, k=K)
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "topology.txt")
        graph_file.write_topology(path, GRAPH_N, edges)
        argv = ["--graph", path, "--k", str(K)]
        _, cold = timed(lambda: spectral_job.main(argv))
        est, warm = timed(lambda: spectral_job.main(argv))
    report_fit(f"graph n={GRAPH_N} edges={GRAPH_EDGES}", est, cold, warm)
    a = ari(truth, np.asarray(est.labels_))
    print(f"  ARI vs planted blocks = {a!r} (CPU: {GRAPH_ARI_CPU!r})")
    check(a >= GRAPH_ARI_CPU - 0.02,
          "graph ARI no more than 0.02 below the CPU run's")
    no_fallback(est)

    # the float64 witness, from the start vector the "lanczos" solver
    # draws: PRNGKey(seed) split three ways, the second key
    _, k_lan, _ = jax.random.split(jax.random.PRNGKey(est.seed), 3)
    v0 = np.asarray(jax.random.normal(k_lan, (est.info_["n_pad"],),
                                      jnp.float32), np.float64)
    steps = est.info_["block_steps"]
    (comps, ref), t_ref = timed(lambda: (
        graph_components(GRAPH_N, edges),
        graph_lanczos_reference(GRAPH_N, edges, v0, steps, K)))
    ev = np.asarray(est.eigenvalues_, np.float64)
    rel = np.max(np.abs(ev[1:] - ref[1:]) / ref[1:])
    print(f"  witness ({t_ref:.3f}s): {comps} connected components, so the "
          f"exact {K} smallest eigenvalues are 0; float64 Lanczos, {steps} "
          f"steps = {ref.tolist()}; max relative |d| over 2..{K} = "
          f"{float(rel)!r}")
    check(abs(ev[0]) <= 1e-5, "the converged eigenvalue is the exact 0 "
          "(within 1e-5)")
    check(ev.min() >= -1e-5, "no eigenvalue below the spectrum's floor 0")
    check(rel <= GRAPH_RITZ_RTOL, f"unconverged eigenvalues within "
          f"{GRAPH_RITZ_RTOL} (relative) of the float64 recurrence")


def phase_ooc(state: dict) -> None:
    from repro.cluster import ari
    from repro.data import synthetic
    from repro.launch import spectral_job

    _, truth = synthetic.blobs(N_OOC, K)        # spectral_job's --blobs data
    with tempfile.TemporaryDirectory() as d:
        argv = ["--engine", "mapreduce", "--blobs", str(N_OOC),
                "--k", str(K), "--chunk-size", str(OOC_CHUNK),
                "--sparsify-t", str(OOC_SPARSIFY_T),
                "--eigensolver", "block-lanczos",
                "--lanczos-steps", str(OOC_LANCZOS_STEPS),
                "--memory-budget", str(OOC_BUDGET), "--spill-dir", d]
        _, cold = timed(lambda: spectral_job.main(argv))
        est, warm = timed(lambda: spectral_job.main(argv))
    report_fit(f"ooc n={N_OOC}", est, cold, warm)
    eng = est.info_["engine"]
    a = ari(truth, np.asarray(est.labels_))
    print(f"  ARI vs planted labels = {a!r} spilled_shards="
          f"{eng['spilled_shards']} retries={eng.get('retries', 0)}")
    check(a >= 0.99, "out-of-core ARI >= 0.99 against the planted labels")
    check(eng["spilled_shards"] > 0, "the memory budget forced spills")
    check(eng.get("retries", 0) == 0, "engine.retries == 0")
    no_fallback(est)


def phase_serve(state: dict) -> None:
    import jax.numpy as jnp

    from repro.cluster import SpectralClustering
    from repro.launch.cluster_serve import (ClusterServer, PredictRequest,
                                            summarize)

    est, x = state["est"], state["x"]
    rng = np.random.RandomState(2)
    queue = []
    for rid in range(SERVE_REQUESTS):
        m = int(rng.randint(100, 1001))
        rows = x[rng.choice(len(x), size=m)] + 0.05 * rng.randn(m, x.shape[1])
        queue.append(PredictRequest(rid=rid, points=rows.astype(np.float32)))
    with tempfile.TemporaryDirectory() as d:
        est.save(d)
        served = SpectralClustering.load(d)
    served.transform_path = "fused"
    srv = ClusterServer(served, batch_rows=SERVE_BATCH)
    t0 = time.perf_counter()
    done = srv.run(queue)
    wall = time.perf_counter() - t0
    s = summarize(done, wall)
    steps = srv.batch_ms.snapshot()
    print(f"  serve (single smoke run, not a benchmark): {s['completed']}/"
          f"{s['requests']} requests, {s['points']} points, {srv.steps} "
          f"steps of {SERVE_BATCH} rows, wall={wall:.3f}s "
          f"p50={s['latency_p50_ms']:.1f}ms p99={s['latency_p99_ms']:.1f}ms "
          f"points/s={s['points_per_s']:.0f}")
    print(f"  steps: slowest={steps['max']:.1f}ms (the first, compiling) "
          f"p50={steps['p50']:.1f}ms")
    check(all(r.status == "ok" and r.done for r in done),
          "every request completed")
    rows = np.concatenate([r.points for r in done])
    want, t_pred = timed(lambda: np.asarray(est.predict(jnp.asarray(rows))))
    got = np.concatenate([r.labels for r in done])
    print(f"  est.predict on the same {len(rows)} rows: {t_pred:.3f}s "
          f"path={est.info_['transform']['path']}")
    check(np.array_equal(got, want), "served labels == est.predict labels")
    check(served.info_["transform"]["schedule"]["interpret"] is False,
          "serving kernel compiled, not interpreted")
    no_fallback()


# -- four chips ---------------------------------------------------------------

def agree(tag: str, est4, est1) -> None:
    from repro.cluster import ari
    a = ari(np.asarray(est1.labels_), np.asarray(est4.labels_))
    e4 = np.asarray(est4.eigenvalues_, np.float64)
    e1 = np.asarray(est1.eigenvalues_, np.float64)
    rel = np.max(np.abs(e4 - e1) / np.maximum(np.abs(e1), 1.0))
    print(f"  {tag}: ARI 4 vs 1 device = {a!r} max relative "
          f"|d eigenvalue| = {rel:.3e}")
    check(a >= 0.999, f"{tag}: label ARI >= 0.999 between 4 and 1 devices")
    check(rel <= 1e-4, f"{tag}: eigenvalues within 1e-4 (relative)")


def peak_memory(tag: str, devices) -> None:
    peaks = [d.memory_stats()["peak_bytes_in_use"] for d in devices]
    print(f"  {tag}: peak_bytes_in_use per device = {peaks}")
    check(all(p > 0 for p in peaks), f"{tag}: every device holds work")


def phase_sharded_fused(state: dict) -> None:
    import jax
    import jax.numpy as jnp

    from repro.distrib import mesh_utils

    x, truth = blobs(N_FIT)
    mesh4 = mesh_utils.local_mesh("rows", n_devices=4)
    est4, t4 = timed(lambda: fused_estimator(mesh4).fit(jnp.asarray(x)))
    report_fit(f"fused-rbf n={N_FIT} on 4 devices", est4, t4, None)
    peak_memory("fused 4-device fit", jax.devices()[:4])
    est1, t1 = timed(lambda: fused_estimator(
        mesh_utils.local_mesh("rows", n_devices=1)).fit(jnp.asarray(x)))
    report_fit(f"fused-rbf n={N_FIT} on 1 device", est1, t1, None)
    agree("fused-rbf", est4, est1)
    no_fallback(est4)


def phase_sharded_triangular(state: dict) -> None:
    import jax
    import jax.numpy as jnp

    from repro.cluster import SpectralClustering
    from repro.distrib import mesh_utils

    x, _ = blobs(N_TRI)

    def fit(n_devices):
        # spectral_job's defaults: triangular / lanczos (48 steps) / lloyd
        return SpectralClustering(
            k=K, affinity="triangular", eigensolver="lanczos",
            assigner="lloyd", lanczos_steps=48,
            mesh=mesh_utils.local_mesh("rows", n_devices=n_devices)
        ).fit(jnp.asarray(x))

    est4, t4 = timed(lambda: fit(4))
    report_fit(f"triangular n={N_TRI} on 4 devices", est4, t4, None)
    peak_memory("triangular 4-device fit", jax.devices()[:4])
    est1, t1 = timed(lambda: fit(1))
    report_fit(f"triangular n={N_TRI} on 1 device", est1, t1, None)
    agree("triangular", est4, est1)
    no_fallback(est4)


ONE_CHIP = (("fused", phase_fused), ("graph", phase_graph),
            ("ooc", phase_ooc), ("serve", phase_serve))
FOUR_CHIPS = (("sharded-fused", phase_sharded_fused),
              ("sharded-triangular", phase_sharded_triangular))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: the four one-chip phases; 4: only the "
                         "sharded fits against one device")
    args = ap.parse_args(argv)

    try:
        from repro import compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repo's src/ is not beside this script ({e})",
              file=sys.stderr)
        return 2
    import jax
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: JAX finds no TPU (backend "
              f"{jax.default_backend()!r}); nothing was run",
              file=sys.stderr)
        return 2
    devices = jax.devices()
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX finds {len(devices)}", file=sys.stderr)
        return 2

    cache_dir = compile_cache.enable()
    print(f"chip_smoke: {len(devices)} x {devices[0].device_kind}, "
          f"compile cache {cache_dir}", flush=True)

    state: dict = {}
    failed = []
    for name, phase in (FOUR_CHIPS if args.chips == 4 else ONE_CHIP):
        print(f"== {name}", flush=True)
        t0, c0 = time.perf_counter(), compile_cache.stats()
        try:
            phase(state)
        except Exception:
            traceback.print_exc()
            failed.append(name)
        c1 = compile_cache.stats()
        print(f"== {name} {'FAILED' if name in failed else 'ok'} "
              f"wall={time.perf_counter() - t0:.3f}s compile cache "
              f"hits={c1['hits'] - c0['hits']} "
              f"misses={c1['misses'] - c0['misses']}", flush=True)
    print(f"compile cache: {compile_cache.stats()}")
    if failed:
        print(f"chip_smoke: failed phases {failed}", file=sys.stderr)
        return 1
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
