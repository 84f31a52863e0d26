"""The observability layer: spans, metrics, and the wiring through the
estimator / engine / serving paths.

Contracts under test (ISSUE 7):
  * spans nest through a thread-local stack (each thread its own), record
    monotonic durations, and tolerate leaked inner spans;
  * histogram percentiles match the numpy nearest-rank oracle exactly
    while every observation is retained (incl. n=1 and n=2 edges);
  * the Chrome-trace export is schema-valid (ph/ts/dur/pid/tid in us,
    metadata events, child spans contained in their parents);
  * metrics snapshots round-trip through to_json, and absorb_stats is
    idempotent (re-absorbing a live dict updates, never double-counts);
  * every fit path (dense / fused-rbf / ooc-topt) publishes
    info_["obs"] with the three phase keys and coverage >= 0.95;
  * refitting the same estimator does NOT accumulate fused-rbf pass
    counters, and a REUSED operator resets to its post-build baseline;
  * summarize() reports correct nearest-rank p50/p95/p99 on small n;
  * JAX's compiles are charged to the spans open on the calling thread
    (inclusive attrs, ``jit.*{span=...}`` counters), to no other thread's,
    and not at all while obs is disabled;
  * ``spectral_job`` nests its job phases and the estimator's spans;
  * the export lines up with the profiler's host plane after one shift;
  * the tracer keeps the newest SPAN_RING spans and counts the dropped.
"""
from __future__ import annotations

import glob
import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.cluster import SpectralClustering
from repro.data import synthetic
from repro.obs.metrics import Histogram, MetricsRegistry, nearest_rank
from repro.obs.trace import Tracer


@pytest.fixture(autouse=True)
def _fresh_obs():
    """Each test sees empty process-wide tracer/registry state."""
    obs.reset()
    yield
    obs.reset()


# -- spans --------------------------------------------------------------------

def test_span_nesting_and_depth():
    tr = Tracer()
    with tr.span("outer") as so:
        with tr.span("inner") as si:
            assert tr.current() is si
            assert si.depth == 1
        assert tr.current() is so
    assert tr.current() is None
    inner, outer = tr.spans()[0], tr.spans()[1]
    assert (inner.name, outer.name) == ("inner", "outer")
    # containment: the child's window lies inside the parent's
    assert outer.t0 <= inner.t0 and inner.t1 <= outer.t1
    assert outer.duration_s >= inner.duration_s >= 0.0


def test_span_decorator_and_attrs():
    tr = Tracer()

    @tr.traced("work.unit", kind="test")
    def work(a, b):
        return a + b

    assert work(2, 3) == 5
    (sp,) = tr.spans()
    assert sp.name == "work.unit" and sp.attrs["kind"] == "test"


def test_span_error_attr_and_leak_tolerance():
    tr = Tracer()
    with pytest.raises(ValueError):
        with tr.span("boom"):
            raise ValueError("x")
    assert tr.spans()[0].attrs["error"] == "ValueError"
    # a leaked (never-exited) inner span must not corrupt the outer pop
    ctx_o = tr.span("outer")
    sp_o = ctx_o.__enter__()
    tr.span("leaked").__enter__()
    ctx_o.__exit__(None, None, None)
    assert tr.current() is None
    assert sp_o.t1 is not None


def test_span_thread_safety():
    tr = Tracer(jax_annotations=False)
    errs = []

    def worker(i):
        try:
            for j in range(25):
                with tr.span(f"t{i}") as sp:
                    with tr.span(f"t{i}.child"):
                        assert tr.current().name == f"t{i}.child"
                    assert tr.current() is sp
        except Exception as e:  # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    assert len(tr.spans()) == 8 * 25 * 2


def test_disabled_tracer_records_nothing():
    tr = Tracer(enabled=False)
    with tr.span("x") as sp:
        sp.set(a=1)        # the null span accepts the same surface
    assert tr.spans() == []


# -- histogram / percentile ---------------------------------------------------

def test_nearest_rank_small_n_edges():
    assert nearest_rank([5.0], 50) == 5.0
    assert nearest_rank([5.0], 99) == 5.0
    # p50 of two samples is the FIRST (rank ceil(0.5*2)=1) — the old
    # len//2 indexing returned the second
    assert nearest_rank([1.0, 2.0], 50) == 1.0
    assert nearest_rank([1.0, 2.0], 99) == 2.0
    assert nearest_rank([], 50) == 0.0


@pytest.mark.parametrize("n", [1, 2, 3, 10, 137])
def test_histogram_matches_numpy_oracle(n):
    rng = np.random.RandomState(n)
    vals = rng.gamma(2.0, 10.0, size=n)
    h = Histogram("lat")
    for v in vals:
        h.observe(v)
    s = np.sort(vals)
    for q in (50, 90, 95, 99, 100):
        oracle = s[min(max(1, int(np.ceil(q / 100 * n))), n) - 1]
        assert h.percentile(q) == pytest.approx(float(oracle))
    snap = h.snapshot()
    assert snap["count"] == n
    assert snap["min"] == pytest.approx(float(s[0]))
    assert snap["max"] == pytest.approx(float(s[-1]))


def test_histogram_beyond_cap_uses_bucket_edges():
    h = Histogram("lat", buckets=(1.0, 10.0, 100.0), sample_cap=4)
    for v in (0.5, 0.5, 5.0, 5.0, 50.0, 50.0):   # 6 obs > cap of 4
        h.observe(v)
    assert h.count == 6
    # estimate is the containing bucket's upper edge: monotone, bounded
    assert h.percentile(50) == 10.0
    assert h.percentile(99) == 100.0


# -- chrome-trace export ------------------------------------------------------

def test_chrome_trace_schema(tmp_path):
    tr = Tracer(jax_annotations=False)
    with tr.span("fit", n=64):
        with tr.span("fit.affinity"):
            pass
    path = str(tmp_path / "sub" / "trace.json")
    tr.export(path)
    doc = json.load(open(path))
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    assert {"process_name", "thread_name"} <= {e["name"] for e in meta}
    xs = {e["name"]: e for e in evs if e["ph"] == "X"}
    assert set(xs) == {"fit", "fit.affinity"}
    parent, child = xs["fit"], xs["fit.affinity"]
    for e in (parent, child):
        assert e["ts"] >= 0 and e["dur"] >= 0
        assert e["pid"] == meta[0]["pid"] and e["tid"] == 0
    # nesting is containment on the tid, in microseconds
    assert parent["ts"] <= child["ts"]
    assert child["ts"] + child["dur"] <= parent["ts"] + parent["dur"] + 1e-3
    assert parent["args"]["n"] == 64
    assert parent["cat"] == "fit"
    # the span that caused each one, and the epoch on both clocks
    assert parent["args"]["parent_id"] is None
    assert child["args"]["parent_id"] == parent["args"]["span_id"]
    assert doc["metadata"] == {"epoch_perf_counter_ns": tr.epoch_ns,
                               "epoch_time_ns": tr.epoch_time_ns}


def test_export_lines_up_with_the_profilers_host_plane(tmp_path):
    """Each span's TraceAnnotation twin is on the profile's host plane;
    shifted by the first span's offset, every start and duration of the
    export agrees with the profile within 1 ms."""
    from jax.profiler import ProfileData

    names = ("align.a", "align.a.b", "align.a.c", "align.a.c.d")
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span(names[0]):
            with obs.span(names[1]):
                time.sleep(0.02)
            with obs.span(names[2]):
                time.sleep(0.01)
                with obs.span(names[3]):
                    time.sleep(0.005)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    host = {}
    for pl in ProfileData.from_file(path).planes:
        if pl.name.startswith("/host:"):
            for ln in pl.lines:
                for e in ln.events:
                    if e.name in names:
                        host[e.name] = (e.start_ns / 1e3, e.duration_ns / 1e3)
    xs = {e["name"]: e for e in obs.tracer.to_chrome_trace()["traceEvents"]
          if e["ph"] == "X"}
    assert set(host) == set(xs) == set(names)
    shift = host[names[0]][0] - xs[names[0]]["ts"]
    for n in names:
        start, dur = host[n]
        assert abs(start - shift - xs[n]["ts"]) <= 1000.0, n
        assert abs(dur - xs[n]["dur"]) <= 1000.0, n


def test_tracer_keeps_the_newest_spans_and_counts_the_dropped():
    obs.tracer.jax_annotations = False
    try:
        for i in range(obs.SPAN_RING + 3):
            with obs.span("ring", i=i):
                pass
    finally:
        obs.tracer.jax_annotations = True
    kept = obs.spans()
    assert len(kept) == obs.SPAN_RING
    assert kept[0].attrs["i"] == 3 and kept[-1].attrs["i"] == obs.SPAN_RING + 2
    assert obs.metrics.get("obs.spans_dropped").value == 3


# -- compile accounting -------------------------------------------------------

def test_first_jit_call_is_charged_to_its_spans():
    def scaled(x):
        return x * 3.0 + 1.0

    f = jax.jit(scaled)
    x = jnp.ones(7)
    with obs.span("outer") as so:
        with obs.span("outer.inner") as si:
            f(x).block_until_ready()
    n, secs = si.attrs["jit_programs"], si.attrs["jit_s"]
    assert n >= 1 and secs > 0
    assert "jit(scaled)" in si.attrs["jit_funs"]
    # inclusive: the parent carries the same amounts
    assert so.attrs["jit_programs"] == n
    assert so.attrs["jit_s"] == pytest.approx(secs)
    assert obs.metrics.get("jit.programs{span=outer.inner}").value == n
    assert obs.metrics.get("jit.traces{span=outer.inner}").value >= 1
    assert obs.metrics.get("jit.programs{span=outer}") is None
    # a cached call compiles nothing
    with obs.span("again") as sa:
        f(x).block_until_ready()
    assert "jit_programs" not in sa.attrs
    assert obs.metrics.get("jit.programs{span=again}") is None


def test_compiles_outside_spans_land_under_span_none():
    def outside(x):
        return x - 2.0

    jax.jit(outside)(jnp.ones(5)).block_until_ready()
    assert obs.metrics.get("jit.programs{span=none}").value >= 1
    assert obs.metrics.get("jit.compile_s{span=none}").value > 0


def test_threads_do_not_charge_each_others_spans():
    started, done = threading.Event(), threading.Event()
    seen = {}

    def worker():
        def in_worker(x):
            return x * x

        started.wait(timeout=30)
        with obs.span("worker") as sp:
            jax.jit(in_worker)(jnp.ones(3)).block_until_ready()
        seen["worker"] = sp
        done.set()

    t = threading.Thread(target=worker)
    t.start()
    with obs.span("main.waiting") as main:
        started.set()
        assert done.wait(timeout=60)
    t.join(timeout=30)
    assert not t.is_alive()
    assert "jit_programs" not in main.attrs
    assert seen["worker"].attrs["jit_programs"] >= 1
    assert obs.metrics.get("jit.programs{span=worker}").value >= 1
    assert obs.metrics.get("jit.programs{span=main.waiting}") is None


def test_compile_accounting_off_while_disabled():
    def quiet(x):
        return x + 4.0

    obs.set_enabled(False)
    try:
        with obs.span("quiet"):
            jax.jit(quiet)(jnp.ones(2)).block_until_ready()
        jax.jit(lambda x: x / 3.0)(jnp.ones(2)).block_until_ready()
    finally:
        obs.set_enabled(True)
    assert obs.metrics.snapshot("jit") == {}


# -- metrics registry ---------------------------------------------------------

def test_metrics_snapshot_round_trip(tmp_path):
    reg = MetricsRegistry()
    reg.counter("a.events").inc(3)
    reg.gauge("a.fill").set(0.5)
    reg.histogram("a.lat_ms").observe(2.0)
    reg.counter("a.events", model="x").inc()          # labeled child
    path = str(tmp_path / "metrics.json")
    text = reg.to_json(path)
    assert json.loads(text) == reg.snapshot()
    assert json.load(open(path)) == reg.snapshot()
    snap = reg.snapshot()
    assert snap["a.events"] == {"type": "counter", "value": 3}
    assert snap["a.events{model=x}"]["value"] == 1
    assert snap["a.lat_ms"]["p50"] == 2.0
    # prefix filtering
    assert set(reg.snapshot("a.events")) == {"a.events", "a.events{model=x}"}


def test_registry_kind_mismatch_raises():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")


def test_absorb_stats_idempotent_and_typed():
    reg = MetricsRegistry()
    stats = {"spills": np.int64(4), "fill": 0.25, "name": "skip",
             "flag": True}
    reg.absorb_stats("store", stats)
    reg.absorb_stats("store", stats)        # re-absorb: update, not double
    snap = reg.snapshot()
    assert snap["store.spills"] == {"type": "counter", "value": 4}
    assert snap["store.fill"] == {"type": "gauge", "value": 0.25}
    assert "store.name" not in snap and "store.flag" not in snap
    stats["spills"] = 9                     # live dict moved on
    reg.absorb_stats("store", stats)
    assert reg.get("store.spills").value == 9


def test_absorb_disabled_is_noop():
    reg = MetricsRegistry(enabled=False)
    reg.absorb_stats("x", {"a": 1})
    assert reg.snapshot() == {}


# -- estimator wiring ---------------------------------------------------------

PTS, _ = synthetic.blobs(96, 3, dim=4, spread=0.08, seed=4)


@pytest.mark.parametrize("affinity", ["dense", "fused-rbf", "ooc-topt"])
def test_fit_publishes_obs_phases(affinity):
    est = SpectralClustering(k=3, affinity=affinity, sigma=1.0,
                             chunk_size=48).fit(jnp.asarray(PTS))
    o = est.info_["obs"]
    assert set(o["phases"]) == {"affinity", "eigensolve", "assign"}
    assert o["coverage"] >= 0.95
    assert o["wall_s"] > 0
    for ph in o["phases"].values():
        assert 0.0 <= ph["frac"] <= 1.0
    # the trace recorded properly nested fit spans...
    names = {s.name for s in obs.spans("fit")}
    assert {"fit", "fit.affinity", "fit.eigensolve", "fit.assign"} <= names
    # ...and the numeric fit stats were mirrored into the registry
    assert obs.metrics.get("fit.matrix_passes").value > 0


def test_refit_does_not_accumulate_fused_counters():
    est = SpectralClustering(k=3, affinity="fused-rbf", sigma=1.0)
    est.fit(jnp.asarray(PTS))
    first = dict(est.info_["obs"]["counters"])
    est.fit(jnp.asarray(PTS))
    second = dict(est.info_["obs"]["counters"])
    assert second["matrix_passes"] == first["matrix_passes"]
    assert second["bytes_streamed"] == first["bytes_streamed"]


def test_reused_operator_resets_to_post_build_baseline():
    from repro.cluster.affinity import build_fused_rbf_operator
    from repro.distrib import mesh_utils

    op = build_fused_rbf_operator(jnp.asarray(PTS, jnp.float32), 1.0,
                                  mesh_utils.local_mesh("rows"))
    base = op.stats_snapshot()["matrix_passes"]
    import jax
    jax.block_until_ready(op.matmat(jnp.ones((op.n_pad, 2), jnp.float32)))
    assert op.stats_snapshot()["matrix_passes"] == base + 1
    op.reset_stats()
    assert op.stats_snapshot()["matrix_passes"] == base


def _tree(spans):
    """{name: span} of one job's spans, and each span's parent span."""
    by_id = {s.sid: s for s in spans}
    return ({s.name: s for s in spans},
            {s.name: by_id[s.parent].name for s in spans
             if s.parent is not None})


@pytest.mark.parametrize("source", ["graph", "points"])
def test_spectral_job_nests_its_phases(source, tmp_path):
    from repro.data import graph_file
    from repro.launch import spectral_job

    if source == "graph":
        edges, _ = synthetic.synthetic_graph(120, 300, k=3, seed=2)
        path = str(tmp_path / "topo.txt")
        graph_file.write_topology(path, 120, edges)
        argv = ["--graph", path, "--k", "3"]
        inputs = {"job.parse", "job.adjacency", "job.to_device"}
        eig = "lanczos"
    else:
        argv = ["--blobs", "96", "--k", "3", "--eigensolver",
                "block-lanczos", "--lanczos-steps", "24"]
        inputs = {"job.data"}
        eig = "block-lanczos"
    est = spectral_job.main(argv)
    assert est.eigensolver == eig
    spans, parent = _tree(obs.spans())
    assert est.info_["obs"]["coverage"] >= 0.95
    for name in inputs | {"fit"}:
        assert parent[name] == "job"
    for name in ("fit.affinity", "fit.eigensolve", "fit.assign"):
        assert parent[name] == "fit"
    for name in ("fit.eigensolve.krylov", "fit.eigensolve.ritz"):
        assert parent[name] == "fit.eigensolve"
    for name in ("fit.assign.seed", "fit.assign.lloyd"):
        assert parent[name] == "fit.assign"
    # children lie inside their parents, on the job's thread
    for name, p in parent.items():
        c, q = spans[name], spans[p]
        assert q.t0 <= c.t0 and c.t1 <= q.t1 and c.tid == q.tid
    # compiles are charged inclusively up to the job, and per phase
    job = spans["job"]
    assert job.attrs["jit_programs"] >= spans["fit"].attrs["jit_programs"] > 0
    phases = est.info_["obs"]["phases"]
    assert sum(p["jit_programs"] for p in phases.values()) \
        <= spans["fit"].attrs["jit_programs"]
    assert all(p["jit_s"] >= 0.0 for p in phases.values())


def test_second_graph_job_compiles_nothing(tmp_path):
    """The graph job's three loops (the Lanczos recurrence, k-means++ and
    Lloyd) compile once per process: a second job of the same shapes
    traces, lowers and loads no program, and gives the same answers; a
    third, on another graph whose rows fill the same width, compiles
    nothing either."""
    from repro.data import graph_file
    from repro.launch import spectral_job

    def job(edges, name):
        path = str(tmp_path / name)
        graph_file.write_topology(path, 90, edges)
        return spectral_job.main(["--graph", path, "--k", "3"])

    def compiled_nothing(edges):
        spans, _ = _tree(obs.spans())
        for name in ("job", "fit.affinity", "fit.eigensolve.krylov",
                     "fit.assign.seed", "fit.assign.lloyd"):
            assert spans[name].attrs.get("jit_programs", 0) == 0, \
                (name, spans[name].attrs.get("jit_funs"))
        # both directions of every edge and 90 self-loops, in rows of
        # one width
        adj = graph_file.adjacency_sparse(90, edges)
        assert spans["job.adjacency"].attrs == {
            "nnz": 2 * len(edges) + 90, "nnz_padded": 90 * adj.width}
        return adj.width

    edges, _ = synthetic.synthetic_graph(90, 220, k=3, seed=5)
    first = job(edges, "topo.txt")
    obs.reset()
    second = job(edges, "topo.txt")
    width = compiled_nothing(edges)
    for got, want in ((second.eigenvalues_, first.eigenvalues_),
                      (second._eigvecs, first._eigvecs),
                      (second.labels_, first.labels_),
                      (second.centers_, first.centers_)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert obs.snapshot()["affinity.graph_fits"]["value"] == 1
    assert second.info_["affinity"] == "graph"
    obs.reset()
    other, _ = synthetic.synthetic_graph(90, 250, k=3, seed=6)
    job(other, "other.txt")
    assert compiled_nothing(other) == width


# -- serving summarize --------------------------------------------------------

def test_summarize_percentiles_small_n():
    from repro.launch.cluster_serve import PredictRequest, summarize

    reqs = []
    for i, lat in enumerate([0.010, 0.020, 0.030]):
        r = PredictRequest(rid=i, points=np.zeros((2, 2), np.float32),
                           labels=np.zeros(2, np.int32), _filled=2)
        r.t_submit, r.t_done = 0.0, lat
        reqs.append(r)
    s = summarize(reqs, wall_s=0.5)
    # nearest-rank over [10, 20, 30] ms: p50 -> 20, p95/p99 -> 30
    assert s["latency_p50_ms"] == pytest.approx(20.0)
    assert s["latency_p95_ms"] == pytest.approx(30.0)
    assert s["latency_p99_ms"] == pytest.approx(30.0)
    assert s["latency_max_ms"] == pytest.approx(30.0)
    assert s["points"] == 6


def test_server_step_feeds_shared_histograms():
    from repro.launch.cluster_serve import ClusterServer, PredictRequest

    est = SpectralClustering(k=3, affinity="dense", sigma=1.0,
                             transform_path="dense").fit(jnp.asarray(PTS))
    srv = ClusterServer(est, batch_rows=32)
    queue = [PredictRequest(rid=i, points=np.asarray(PTS[:20], np.float32))
             for i in range(3)]
    srv.run(queue)
    assert srv.request_ms.count == 3
    assert srv.batch_ms.count == srv.stats["batches"] > 0
    snap = obs.metrics.snapshot("serve")
    assert snap["serve.request_ms"]["p99"] >= snap["serve.request_ms"]["p50"]
    assert 0.0 < snap["serve.fill"]["value"] <= 1.0
    assert {s.name for s in obs.spans("serve")} == {"serve.step"}


# -- toggling -----------------------------------------------------------------

def test_set_enabled_false_silences_everything():
    obs.set_enabled(False)
    try:
        with obs.span("quiet"):
            obs.absorb_stats("q", {"a": 1})
        assert obs.spans() == []
        assert obs.metrics.snapshot("q") == {}
    finally:
        obs.set_enabled(True)
