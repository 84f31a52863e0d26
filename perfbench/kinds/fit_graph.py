"""The paper's graph job, back to back: a topology file on disk in,
labels on the host out, through ``spectral_job --graph`` (the
precomputed dense affinity, single-vector Lanczos, Lloyd).

The graph is a planted partition drawn from the seed at the
configuration's sizes: ``k`` blocks of uniformly drawn vertices, and
``edges`` distinct undirected edges, each inside its first endpoint's
block with probability ``p_in`` and between blocks otherwise.
"""
from __future__ import annotations

import contextlib
import os
import sys
import tempfile
import time

import numpy as np

from perfbench import harness, reference as ref


def planted_partition(cfg: dict, seed: int):
    """(edges (m, 3) int64 [i, j, 1] with i < j, block of each vertex)."""
    rng = np.random.default_rng(seed)
    n, m, k = cfg["vertices"], cfg["edges"], cfg["k"]
    block = rng.integers(0, k, n)
    order = np.argsort(block, kind="stable")
    size = np.bincount(block, minlength=k)
    start = np.concatenate([[0], np.cumsum(size)[:-1]])
    seen: set = set()
    rows = []
    while len(rows) < m:
        draw = 4 * (m - len(rows)) + 64
        i = rng.integers(0, n, draw)
        b = block[i]
        same = rng.random(draw) < cfg["p_in"]
        inside = order[start[b] + (rng.random(draw) * size[b]).astype(
            np.int64)]
        other = order[(start[b] + size[b] + (rng.random(draw) * (
            n - size[b])).astype(np.int64)) % n]
        j = np.where(same, inside, other)
        for a, c in zip(np.minimum(i, j).tolist(), np.maximum(i, j).tolist()):
            if a != c and (a, c) not in seen:
                seen.add((a, c))
                rows.append((a, c, 1))
                if len(rows) == m:
                    break
    return np.asarray(rows, np.int64), block


def write_topology(path: str, n: int, edges: np.ndarray) -> None:
    """The paper's section 5.1 text format: ``t``, ``v id label``,
    ``e src dst weight`` lines."""
    lines = ["t # 0\n"]
    lines += [f"v {i} 0\n" for i in range(n)]
    lines += [f"e {i} {j} {w}\n" for i, j, w in edges.tolist()]
    with open(path, "w") as f:
        f.writelines(lines)


class State:
    def __init__(self, cfg, edges, path, tmp):
        self.cfg, self.edges, self.path, self._tmp = cfg, edges, path, tmp
        self.jobs = []


def _job(state, ctx):
    from repro.launch import spectral_job
    argv = ["--graph", state.path, "--k", str(state.cfg["k"])]
    t0 = time.perf_counter()
    with ctx.span("bench.graph_job"), \
            contextlib.redirect_stdout(sys.stderr):
        est = spectral_job.main(argv)
        labels = np.asarray(est.labels_)
    dt = time.perf_counter() - t0
    return dt, {"labels": labels, "evals": est.eigenvalues_,
                "Z": est._eigvecs, "centers": est.centers_,
                "obs": est.info_.get("obs", {}),
                "passes": int(est.info_.get("matrix_passes", 0))}


def setup(cell, seed: int, devices, ctx) -> State:
    if len(devices) != 1:
        raise ValueError("the graph job runs on one chip")
    edges, _ = planted_partition(cell.config, seed)
    tmp = tempfile.TemporaryDirectory()
    path = os.path.join(tmp.name, "topology.txt")
    write_topology(path, cell.config["vertices"], edges)
    state = State(cell.config, edges, path, tmp)
    _job(state, ctx)                    # warm every program the jobs use
    return state


def window(state: State, seconds: float, ctx) -> dict:
    state.jobs, failed = harness.back_to_back(lambda: _job(state, ctx),
                                              seconds, ctx)
    secs = [dt for dt, _ in state.jobs]
    return {"attempted": len(secs) + failed, "failed": failed,
            "e2e": {"fit_s": sum(secs) / len(secs)} if secs else {}}


def release(state: State) -> None:
    for _, out in state.jobs:
        for key in ("evals", "Z", "centers"):
            out[key] = np.asarray(out[key], np.float64)
    state._tmp.cleanup()


def _nan_first(v):
    return (v != v, v if v == v else 0.0)


def check(state: State, ctx) -> dict:
    """Each job's eigenpairs against the float64 operator of the same
    edges, and its labels against the float64 nearest of its centers.
    The widest reading over the window's jobs."""
    n = state.cfg["vertices"]
    _, apply_n = ref.graph_operator(n, state.edges)
    readings: dict = {}
    for _, out in state.jobs:
        got = ref.eigen_numbers(out["evals"], out["Z"], apply_n)
        if np.isnan(got["ritz_gap"]):           # no eigenvectors to embed
            got["label_gap"] = float("nan")
        else:
            got["label_gap"] = float(np.max(ref.assign_gaps(
                ref.normalize_rows(out["Z"]), out["centers"],
                out["labels"])))
        for name, v in got.items():             # NaN, once read, stays
            readings[name] = max(readings.get(name, v), v, key=_nan_first)
    return readings
