"""The embedding cell's comparison, run through the harness at a size a
test run can hold: a sound run comes out correct, and each control comes
out not correct.

The chip runs the controls at the cell's own size
(``perfbench/tools/controls_embed.py``); here the harness's look for a
chip is skipped, the CPU's first device stands in, and the fused kernel
runs in interpret mode.
"""
import copy

import pytest

from perfbench import controls_embed, harness
from perfbench import run as bench_run

SEED = 2 ** 31 + 13
WORKLOAD = "fit.mteb-embed-d4096"


def small():
    """The cell at 2,048 rows of width 1,280 (two feature tiles) and 8
    topics."""
    cell = copy.deepcopy(harness.find_cell(harness.load_benchmark(),
                                           WORKLOAD))
    cell.config.update(n=2048, d=1280, k=8)
    return cell


def run(cell, seconds=0.1):
    import jax
    return bench_run.run(cell, SEED, seconds, False, jax.devices()[:1])


def test_sound_run_is_correct():
    res = run(small())
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert {"setup_s", "fit_s"} <= set(res["metrics"])
    assert {"orth", "ari"} <= set(res["readings"])
    # the reference's device degrees agree with its float64 rows on R
    assert res["readings"]["deg_ref_gap"] < 1e-6


CONTROLS = {     # each control, and the number of the check it fails
    "tile_bf16": (controls_embed.tile_bf16, "deg_rel"),
    "tile_bf16_wide": (controls_embed.tile_bf16_wide, "pass_rel"),
    "passes1": (lambda: controls_embed.products_at(1), "resid_k"),
    "gram_drops_last_dtile": (controls_embed.gram_drops_last_dtile,
                              "deg_rel"),
    "half_rows": (controls_embed.half_rows, "resid_k"),
    "labels_altered": (controls_embed.labels_altered, "label_gap"),
}


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_a_control_is_not_correct(control):
    make, number = CONTROLS[control]
    with make():
        res = run(small())
    assert not res["correct"], res["checks"]
    check = res["checks"][number]
    assert check["value"] is None or check["value"] > check["limit"]


def test_reference_exp_holds_float32():
    """The reference's own exp is within 1e-7 of float64's over the RBF's
    arguments, whatever the device's exp reads."""
    import jax.numpy as jnp
    import numpy as np

    from perfbench import reference_embed as ref
    a = np.linspace(-30.0, 0.0, 100001).astype(np.float32)
    got = np.asarray(ref.exp32(jnp.asarray(a)), np.float64)
    assert np.max(np.abs(got / np.exp(a.astype(np.float64)) - 1)) < 1e-7


def test_deg_rel_sees_every_row():
    """A degree gone wrong on a row outside the sample still fails
    ``deg_rel``: the reference's degrees cover every row."""
    import numpy as np

    from perfbench import reference_embed as ref
    rng = np.random.default_rng(5)
    x = rng.standard_normal((512, 96))
    x = (x / np.linalg.norm(x, axis=1, keepdims=True)).astype(np.float32)
    sigma = ref.median_sigma(x)
    sample = ref.sample_rows(512, 64, 9)
    S_R = ref.affinity_rows(x, sample, sigma)
    deg = ref.degrees(x, sigma)
    np.testing.assert_allclose(deg[sample], S_R.sum(1), rtol=1e-6)
    Z = np.linalg.qr(rng.standard_normal((512, 4)))[0]
    out = {"inv_sqrt": deg ** -0.5, "Z": Z, "evals": np.zeros(4),
           "centers": np.eye(4), "labels": np.zeros(512, int)}
    sound = ref.job_numbers(S_R, sample, deg, out, out["labels"])
    row = int(np.setdiff1d(np.arange(512), sample)[0])
    out["inv_sqrt"] = out["inv_sqrt"].copy()
    out["inv_sqrt"][row] *= 1.0 + 5e-5
    moved = ref.job_numbers(S_R, sample, deg, out, out["labels"])
    assert sound["deg_rel"] < 1e-12
    assert moved["deg_rel"] == pytest.approx(1e-4, rel=1e-3)


# ---------------------------------------------------------------------------
# the cell's per-layer readers


def ctx_with(widths, trace=None):
    cell = harness.find_cell(harness.load_benchmark(), WORKLOAD)
    ctx = harness.Context(cell=cell, seed=0, trace=trace)
    ctx.counters["assign_s"] = [0.1] * len(widths)
    ctx.counters["fused_widths"] = widths
    return ctx


def test_input_reader_takes_the_window_jobs_only():
    from repro import obs
    obs.reset()
    for _ in range(3):                          # set-up's job, then two
        with obs.span("job"):
            for name in ("job.load", "job.to_device"):
                with obs.span(name):
                    pass
            with obs.span("fit"):
                pass
    spans = obs.spans()
    want = [sum(s.duration_s for s in spans if s.parent == job.sid
                and s.name in ("job.load", "job.to_device"))
            for job in [s for s in spans if s.name == "job"][1:]]
    got = harness.metric_reader("embed_input_s")(ctx_with([None, None]))
    obs.reset()
    assert got == pytest.approx(sum(want) / 2, rel=1e-9, abs=1e-12)


def test_fused_readers(monkeypatch):
    import jax

    from perfbench import fused_counts, trace_reduce
    monkeypatch.setitem(fused_counts.PEAKS, jax.devices()[0].device_kind,
                        fused_counts.PEAKS["TPU v5 lite"])
    trace = trace_reduce.Reduced(window_s=10.0, busy_s=6.0,
                                 kernel_s={"fused_rbf": 2.4})
    ctx = ctx_with([{1: 1, 64: 5}, {1: 1, 64: 5}], trace)
    read = harness.metric_reader
    assert read("fused_passes")(ctx) == 6
    assert read("fused_pass_s")(ctx) == pytest.approx(0.2)
    n, d = 65536, 4096
    flop = 2 * (2 * n * n * (d + 1) + 5 * 2 * n * n * (d + 64))
    assert read("fused_roofline_pct")(ctx) == pytest.approx(
        100 * flop / 197e12 / 2.4)
    assert read("device_idle_pct.fit")(ctx) == pytest.approx(40.0)


def test_fused_readers_report_nothing_without_the_counters():
    """A program without ``fused.passes`` counters (the parent commit):
    the readers report nothing and do not raise."""
    from perfbench import trace_reduce
    trace = trace_reduce.Reduced(window_s=10.0, busy_s=6.0,
                                 kernel_s={"fused_rbf": 2.4})
    for name in ("fused_passes", "fused_pass_s", "fused_roofline_pct"):
        assert harness.metric_reader(name)(ctx_with([None, None],
                                                    trace)) is None
    assert harness.metric_reader("embed_input_s")(ctx_with([])) is None
