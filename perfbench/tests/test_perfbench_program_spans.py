"""The per-layer readers of the program's own spans, on a context whose
spans were recorded here: they read the window's jobs only (not set-up's
job, not a failed one), and report nothing when there are no job spans."""
import pytest

from perfbench import harness


@pytest.fixture
def obs():
    from repro import obs
    obs.reset()
    yield obs
    obs.reset()


def ctx_with(window_jobs: int):
    cell = harness.find_cell(harness.load_benchmark(), "fit.paper-graph")
    ctx = harness.Context(cell=cell, seed=0)
    ctx.counters["assign_s"] = [0.1] * window_jobs
    return ctx


def record_job(obs, jit_s: float, programs: int, fail: bool = False):
    """One job's spans as ``spectral_job --graph`` nests them."""
    try:
        with obs.span("job") as job:
            for name in ("job.parse", "job.adjacency", "job.to_device"):
                with obs.span(name):
                    pass
            if fail:
                raise RuntimeError("a failed job")
            with obs.span("fit"):
                with obs.span("fit.affinity"):
                    pass
                with obs.span("fit.assign"):
                    with obs.span("fit.assign.seed"):
                        pass
            job.set(jit_s=jit_s, jit_programs=programs)
    except RuntimeError:
        pass


def per_job(obs, names):
    """{job span id: seconds of the spans named in ``names`` under it}."""
    spans = obs.spans()
    by_id = {s.sid: s for s in spans}
    out = {s.sid: 0.0 for s in spans if s.name == "job"}
    for s in spans:
        p = s.parent
        while p is not None and by_id[p].name != "job":
            p = by_id[p].parent
        if s.name in names and p is not None:
            out[p] += s.duration_s
    return out


EXPECTED = {
    "input_s": lambda obs, jobs: [
        per_job(obs, ("job.parse", "job.adjacency", "job.to_device"))[j.sid]
        for j in jobs],
    "affinity_s": lambda obs, jobs: [
        per_job(obs, ("fit.affinity",))[j.sid] for j in jobs],
    "jit_s": lambda obs, jobs: [j.attrs["jit_s"] for j in jobs],
    "jit_programs": lambda obs, jobs: [j.attrs["jit_programs"] for j in jobs],
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_takes_the_window_jobs_only(obs, name):
    record_job(obs, jit_s=9.0, programs=90)            # set-up's job
    record_job(obs, jit_s=0.25, programs=3)
    record_job(obs, jit_s=7.0, programs=70, fail=True)
    record_job(obs, jit_s=0.75, programs=5)
    window = [s for s in obs.spans("job") if s.name == "job"
              and "error" not in s.attrs][1:]
    want = EXPECTED[name](obs, window)
    got = harness.metric_reader(name)(ctx_with(2))
    assert got == pytest.approx(sum(want) / 2, rel=1e-9, abs=1e-12)
    if name == "jit_programs":
        assert got == 4
    if name == "jit_s":
        assert got == pytest.approx(0.5)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_reports_nothing_without_job_spans(obs, name):
    read = harness.metric_reader(name)
    assert read(ctx_with(3)) is None           # no spans at all
    with obs.span("fit"):                      # spans, but no job
        with obs.span("fit.affinity"):
            pass
    assert read(ctx_with(3)) is None
    record_job(obs, jit_s=1.0, programs=1)
    assert read(ctx_with(0)) is None           # no window jobs
