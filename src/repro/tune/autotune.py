"""Schedule autotuner: sweep legal candidates per (kernel, shape bucket,
device), timing REAL kernel calls through the public ``ops`` wrappers,
score them against the roofline peak model, and persist the winner in the
schedule cache.

The candidate grid is small on purpose (tile edges from the MXU-multiple
ladder, accumulator placement, grid order): the point is not exhaustive
search but moving each kernel from "whatever 128/256 guess was hard-coded"
to "the best of the legal ladder for THIS shape on THIS device".  The
default schedule is always among the candidates, so the tuned pick can
never regress it (up to timing noise — winners are best-of-``iters``).

``autotune`` returns a full report (every candidate with wall time and
achieved-vs-peak FLOPs/bytes via ``repro.tune.peaks``); ``tune_all``
sweeps the standard kernel set.  A cache hit short-circuits the sweep
unless ``force=True`` — re-running a sweep is free once tuned.
"""
from __future__ import annotations

import time
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.tune.cache import ScheduleCache, bucket, default_cache
from repro.tune.peaks import kernel_roofline
from repro.tune.schedule import Schedule, ScheduleError, spec

# tile-edge ladder: MXU/lane multiples only (every entry legal compiled)
TILE_LADDER = (128, 256, 512)
QUICK_TILES = (128, 256)
# feature-tile ladder of the kernels with a feature grid axis (tiles
# narrower than the row only; the default rule's tile is always tried)
D_TILE_LADDER = (256, 512, 1024)

# the standard sweep set: every schedulable kernel with a nominal shape
# builder (n is the sweep variable; d/b/k are the repo's workhorse sizes)
SWEEP_KERNELS = ("rbf_similarity", "fused_rbf_matmat",
                 "fused_nystrom_matmat", "block_matmat", "kmeans_assign")


def _kernel_shape(kernel: str, n: int, *, d: int = 8, b: int = 8,
                  k: int = 8) -> dict:
    return {
        "rbf_similarity": {"n": n, "m": n, "d": d},
        "fused_rbf_matmat": {"n": n, "m": n, "d": d, "b": b},
        "fused_nystrom_matmat": {"n": n, "m": n, "d": d, "b": b},
        "block_matmat": {"n": n, "m": n, "b": b},
        "kmeans_assign": {"n": n, "d": d, "k": k},
    }[kernel]


def candidates(kernel: str, *, quick: bool = False,
               compute_dtype: Optional[str] = None,
               interpret: Optional[bool] = None, **shape) -> list:
    """Legal schedule candidates for one kernel/shape (default included,
    always first).  Tiles larger than the padded problem edge are skipped
    (they only add padding work); illegal combinations are filtered by the
    spec's own legality check.  A kernel with a feature grid axis also
    tries feature tiles narrower than the row (``D_TILE_LADDER``) beside
    the default rule's."""
    sp = spec(kernel)
    tiles = QUICK_TILES if quick else TILE_LADDER
    n_cap = bucket(int(shape.get("n", tiles[-1])))
    m_cap = bucket(int(shape.get("m", tiles[-1])))
    bms = sorted({t for t in tiles if t <= max(n_cap, tiles[0])})
    bns = sorted({t for t in tiles if t <= max(m_cap, tiles[0])}) \
        if sp.has_bn else [None]
    bds = [None]
    if sp.has_bd and "d" in shape:
        from repro.kernels.fused_rbf_matmat import default_d_tile
        d = int(shape["d"])
        bds = [default_d_tile(d, int(shape.get("itemsize", 4)))]
        if not quick:
            bds += [t for t in D_TILE_LADDER if t < d and t not in bds]
    accs = ("inplace",) if (quick or not sp.reduces) \
        else ("inplace", "scratch")
    orders = ("row-major",) if (sp.reduces or not sp.has_bn or quick) \
        else ("row-major", "col-major")

    base = sp.default.replace(
        bd=bds[0], interpret=interpret,
        compute_dtype=compute_dtype if sp.has_compute_dtype else None)
    out = [base]
    for bm in bms:
        for bn in bns:
            for bd in bds:
                for acc in accs:
                    for order in orders:
                        s = base.replace(bm=bm, bn=bn, bd=bd, acc=acc,
                                         grid_order=order)
                        if s in out:
                            continue
                        try:
                            sp.check(s.replace(
                                interpret=s.interpret
                                if s.interpret is not None else True),
                                **shape)
                        except ScheduleError:
                            continue
                        out.append(s)
    return out


def _bench_fn(kernel: str, **shape):
    """A closure running one real call of the kernel's public wrapper on
    synthetic data of the given shape (data built once, outside timing)."""
    from repro.kernels import ops

    def rand(shp, seed):
        return jax.random.normal(jax.random.PRNGKey(seed), shp, jnp.float32)

    n, m = shape.get("n", 0), shape.get("m", 0)
    d, b, k = shape.get("d", 8), shape.get("b", 8), shape.get("k", 8)
    if kernel == "rbf_similarity":
        x, y = rand((n, d), 0), rand((m, d), 1)
        return lambda s: ops.rbf_similarity(x, y, 1.0, schedule=s)
    if kernel == "fused_rbf_matmat":
        x, y, V = rand((n, d), 0), rand((m, d), 1), rand((m, b), 2)
        return lambda s: ops.fused_rbf_matmat(x, y, V, 1.0, schedule=s)
    if kernel == "fused_nystrom_matmat":
        x, y, V = rand((m, d), 0), rand((n, d), 1), rand((n, b), 2)
        cs = jnp.ones((n,), jnp.float32)
        return lambda s: ops.fused_nystrom_matmat(x, y, V, 1.0, cs,
                                                  schedule=s)[0]
    if kernel == "block_matmat":
        A, V = rand((n, m), 0), rand((m, b), 1)
        return lambda s: ops.block_matmat(A, V, schedule=s)
    if kernel == "kmeans_assign":
        p, c = rand((n, d), 0), rand((k, d), 1)
        return lambda s: ops.kmeans_assign(p, c, schedule=s)[1]
    raise ScheduleError(f"no benchmark harness for kernel {kernel!r}")


def _time(fn, s: Schedule, *, warmup: int, iters: int) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(s))
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(s))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def autotune(kernel: str, n: int, *, d: int = 8, b: int = 8, k: int = 8,
             compute_dtype: Optional[str] = None,
             cache: Optional[ScheduleCache] = None, quick: bool = False,
             force: bool = False, warmup: int = 1, iters: int = 3,
             log: Any = None) -> dict:
    """Tune one kernel at one shape; returns the report dict and stores
    the winner in ``cache`` (default: the process cache).

    Report: ``{"kernel", "shape", "cache_hit", "default_us", "best_us",
    "speedup", "best" (schedule dict), "rows": [per-candidate {schedule,
    wall_us, gflops, frac_peak_flops, gbs, frac_peak_bytes}]}``.
    """
    from repro import obs

    cache = cache or default_cache()
    shape = _kernel_shape(kernel, n, d=d, b=b, k=k)
    dtype = compute_dtype or "float32"
    sp = spec(kernel)

    with obs.span("tune.autotune", kernel=kernel, n=n) as sp_tune:
        if not force:
            hit = cache.entry(kernel, dtype=dtype, **shape)
            if hit is not None:
                rep = {"kernel": kernel, "shape": shape, "cache_hit": True,
                       "best": hit["schedule"],
                       "best_us": hit.get("wall_us"),
                       "default_us": hit.get("default_wall_us"), "rows": []}
                if log:
                    log(f"tune/{kernel}_n{n}: cache_hit=True "
                        f"schedule={hit['schedule']}")
                sp_tune.set(cache_hit=True)
                obs.absorb_stats("tune.cache", cache.stats)
                return rep

        fn = _bench_fn(kernel, **shape)
        cands = candidates(kernel, quick=quick, compute_dtype=compute_dtype,
                           **shape)
        if quick:
            iters = 1
        rows, default_us = [], None
        for s in cands:
            wall_us = _time(fn, s, warmup=warmup, iters=iters)
            rec = {"schedule": s.to_dict(), "wall_us": round(wall_us, 1)}
            if sp.flops_model and sp.bytes_model:
                rec.update(kernel_roofline(
                    sp.flops_model(s, **shape), sp.bytes_model(s, **shape),
                    wall_us * 1e-6))
            rows.append(rec)
            if default_us is None:
                default_us = wall_us        # candidate 0 IS the default
            if log:
                log(f"tune/{kernel}_n{n}: bm={s.bm} bn={s.bn} bd={s.bd} "
                    f"acc={s.acc} "
                    f"order={s.grid_order} -> {wall_us:.0f}us")
        best_i = min(range(len(rows)), key=lambda i: rows[i]["wall_us"])
        best = cands[best_i]
        best_us = rows[best_i]["wall_us"]
        cache.put(kernel, best, dtype=dtype, wall_us=best_us,
                  default_wall_us=default_us, **shape)
        sp_tune.set(cache_hit=False, candidates=len(cands))
        obs.counter("tune.candidates_timed").inc(len(cands))
        obs.absorb_stats("tune.cache", cache.stats)
    return {"kernel": kernel, "shape": shape, "cache_hit": False,
            "default_us": round(default_us, 1),
            "best_us": round(best_us, 1),
            "speedup": round(default_us / max(best_us, 1e-9), 3),
            "best": best.to_dict(), "rows": rows}


def tune_all(ns=(1024, 4096), *, kernels=SWEEP_KERNELS, d: int = 8,
             b: int = 8, k: int = 8, cache: Optional[ScheduleCache] = None,
             quick: bool = False, force: bool = False,
             log: Any = None) -> list:
    """The standard sweep: every schedulable kernel at each n.  Returns
    the list of :func:`autotune` reports (cache hits included)."""
    reports = []
    for kernel in kernels:
        for n in ns:
            reports.append(autotune(kernel, n, d=d, b=b, k=k, cache=cache,
                                    quick=quick, force=force, log=log))
    return reports
