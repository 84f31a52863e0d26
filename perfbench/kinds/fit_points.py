"""Embedding clustering jobs, back to back: a ``.npy`` of bf16 rows on
disk in, labels on the host out, through ``spectral_job --points`` (the
matrix-free fused RBF affinity, block Lanczos, Lloyd) with the
configuration's ``job`` flags.

The rows are drawn from the seed on the device, in bulk: unit rows
``normalize(a u0 + b mu_c + c g)`` (the configuration's ``generator``:
``shared`` = a^2, ``topic`` = b^2, ``noise`` = c^2) with each row's topic
c drawn with probability proportional to rank^-zipf, rounded once to
bf16.  That rounded array is what the job reads from disk and what the
reference (``perfbench/reference_embed.py``) reads in float64.
"""
from __future__ import annotations

import contextlib
import os
import sys
import tempfile
import time

import numpy as np

from perfbench import harness, reference_embed as ref


def embeddings(cfg: dict, seed: int):
    """(rows (n, d) bf16 numpy, topic of each row (n,) int)."""
    import jax
    import jax.numpy as jnp

    n, d, k = cfg["n"], cfg["d"], cfg["k"]
    gen = cfg["generator"]
    # any integer seed, past 32 bits too, to one threefry key
    words = np.random.SeedSequence(seed).generate_state(2)
    key = jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))
    p = np.arange(1, k + 1, dtype=np.float64) ** -gen["zipf"]
    p = jnp.asarray(p / p.sum(), jnp.float32)
    amp = [float(np.sqrt(gen[g])) for g in ("shared", "topic", "noise")]

    @jax.jit
    def draw(key):
        kt, ku, km, kg = jax.random.split(key, 4)

        def unit(v):
            return v / jnp.linalg.norm(v, axis=-1, keepdims=True)

        topic = jax.random.choice(kt, k, (n,), p=p)
        u0 = unit(jax.random.normal(ku, (d,)))
        mu = unit(jax.random.normal(km, (k, d)))
        g = jax.random.normal(kg, (n, d)) / np.sqrt(d)
        x = amp[0] * u0[None, :] + amp[1] * mu[topic] + amp[2] * g
        return unit(x).astype(jnp.bfloat16), topic

    x, topic = draw(key)
    return np.asarray(x), np.asarray(topic)


def job_argv(cfg: dict, path: str) -> list:
    return [path if a == "<points file>" else str(cfg["k"]) if a == "<k>"
            else a for a in cfg["job"]]


class State:
    def __init__(self, cfg, x, topics, path, tmp):
        self.cfg, self.x, self.topics = cfg, x, topics
        self.path, self._tmp = path, tmp
        self.jobs = []


def _fused_pass_counts() -> dict | None:
    """{width: passes} of the program's ``fused.passes{width=...}``
    counters; None where the program has no such counter."""
    from repro import obs
    out = {}
    for name, m in obs.snapshot().items():
        if name.startswith("fused.passes{width="):
            out[int(name[len("fused.passes{width="):-1])] = m["value"]
    return out or None


def _job(state, ctx):
    from repro.launch import spectral_job
    argv = job_argv(state.cfg, state.path)
    before = _fused_pass_counts() or {}
    t0 = time.perf_counter()
    with ctx.span("bench.points_job"), \
            contextlib.redirect_stdout(sys.stderr):
        try:
            est = spectral_job.main(argv)
        except SystemExit as e:     # argparse: a failed job, not an exit
            raise RuntimeError(f"spectral_job refused {argv} (exit "
                               f"{e.code})") from None
        labels = np.asarray(est.labels_)
    dt = time.perf_counter() - t0
    after = _fused_pass_counts()
    widths = None if after is None else {
        w: c - before.get(w, 0) for w, c in after.items()
        if c - before.get(w, 0)}
    return dt, {"labels": labels, "evals": est.eigenvalues_,
                "Z": est._eigvecs, "inv_sqrt": est._inv_sqrt,
                "centers": est.centers_, "sigma": float(est.sigma_),
                "obs": est.info_.get("obs", {}),
                "passes": int(est.info_.get("matrix_passes", 0)),
                "fused_widths": widths}


def setup(cell, seed: int, devices, ctx) -> State:
    if len(devices) != 1:
        raise ValueError("the embedding job runs on one chip")
    x, topics = embeddings(cell.config, seed)
    tmp = tempfile.TemporaryDirectory()
    path = os.path.join(tmp.name, "embeddings.npy")
    np.save(path, x)
    state = State(cell.config, x, topics, path, tmp)
    _job(state, ctx)                    # warm every program the jobs use
    return state


def window(state: State, seconds: float, ctx) -> dict:
    state.jobs, failed = harness.back_to_back(lambda: _job(state, ctx),
                                              seconds, ctx)
    for _, out in state.jobs:
        ctx.counters.setdefault("fused_widths", []).append(
            out["fused_widths"])
    secs = [dt for dt, _ in state.jobs]
    return {"attempted": len(secs) + failed, "failed": failed,
            "e2e": {"fit_s": sum(secs) / len(secs)} if secs else {}}


def release(state: State) -> None:
    for _, out in state.jobs:
        for key in ("evals", "Z", "inv_sqrt", "centers"):
            out[key] = np.asarray(out[key], np.float64)
    state._tmp.cleanup()


def block_width(cfg: dict) -> int:
    return int(cfg["job"][cfg["job"].index("--block-size") + 1])


def one_pass(state: State, sigma: float, seed: int):
    """(V, V + N V): one pass at the job's block width of the operator
    the program builds for the job's rows and sigma (``fused-rbf`` with
    ``spectral_job``'s defaults), on seeded Gaussian columns."""
    import jax.numpy as jnp

    from repro.cluster.affinity import build_fused_rbf_operator
    from repro.distrib import mesh_utils
    n = state.cfg["n"]
    rng = np.random.default_rng([seed, 0x9a55])
    V = (rng.standard_normal((n, block_width(state.cfg)))
         / np.sqrt(n)).astype(np.float32)
    op = build_fused_rbf_operator(jnp.asarray(state.x), sigma,
                                  mesh_utils.local_mesh("rows"))
    Vp = jnp.zeros((op.n_pad, V.shape[1]), jnp.float32).at[:n].set(V)
    return V, np.asarray(op.matmat(Vp))[:n]


def check(state: State, ctx) -> dict:
    """Each job's degrees against the reference's on all rows, its
    eigenpairs against float64 rows of the affinity on a seeded sample
    of rows, and its labels against the float64 nearest of its centers;
    then one pass of the program's operator at the last job's sigma.
    The widest reading over the window's jobs."""
    cfg = state.cfg
    sample = ref.sample_rows(cfg["n"], cfg["sample_rows"], ctx.seed)
    sigma = ref.median_sigma(state.x)
    S_R = ref.affinity_rows(state.x, sample, sigma)
    deg = ref.degrees(state.x, sigma)
    deg_R = S_R.sum(axis=1)
    readings: dict = {"deg_ref_gap": float(np.max(
        np.abs(deg[sample] - deg_R) / deg_R))}
    deg[sample] = deg_R
    for _, out in state.jobs:
        got = ref.job_numbers(S_R, sample, deg, out, state.topics)
        for name, v in got.items():             # NaN, once read, stays
            worst = min if name == "ari" else max   # ari: higher is better
            readings[name] = worst(readings.get(name, v), v,
                                   key=ref.nan_first)
    if state.jobs:
        V, VNV = one_pass(state, state.jobs[-1][1]["sigma"], ctx.seed)
        readings["pass_rel"] = ref.pass_gap(S_R, sample, deg, V, VNV)
    return readings
