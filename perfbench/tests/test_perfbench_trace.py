"""The trace reduction, on a hand-built trace and on a trace recorded on
a TPU v5e (``tests/data/small_trace.xplane.pb.gz``: one fused fit at
n=16,384 and 0.3 s of serve steps against 16,384 training points, one
chip, the harness's ``bench.window`` span around both)."""
import os
from types import SimpleNamespace as NS

import pytest

from perfbench import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "small_trace.xplane.pb.gz")


def ev(name, start, dur, stats=()):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur),
              stats=list(stats))


def plane(name, lines):
    return NS(name=name, lines=[NS(name=n, events=e) for n, e in lines])


def fake_trace():
    """Window [100, 1100] ns on the host; two devices."""
    host = plane("/host:CPU", [("python", [
        ev(tr.WINDOW, 100, 1000),
        ev("bench.fit_job", 100, 1000),
        ev("fit.eigensolve", 150, 500),
        ev("fit.assign", 700, 400),
        ev("$python frame", 0, 2000)])])
    dev0 = plane("/device:TPU:0", [(tr.OPS_LINE, [
        ev("custom-call.1", 50, 150, [("kernel", "_fused_kernel_x")]),
        ev("custom-call.1", 300, 200, [("kernel", "_fused_kernel_x")]),
        ev("fusion.2", 450, 100),          # overlaps the kernel above
        ev("all-reduce.3", 800, 100),
        ev("_nystrom_kernel", 1050, 100)])])
    dev1 = plane("/device:TPU:1", [(tr.OPS_LINE, [
        ev("custom-call.1", 100, 500, [("kernel", "_fused_kernel_x")])])])
    return NS(planes=[host, dev1, dev0])


def test_busy_idle_kernels_and_all_reduce():
    r = tr.reduce(fake_trace(), 2)
    assert r.window_s == pytest.approx(1000e-9)
    # device 0 busy: [100,200] [300,550] [800,900] [1050,1100] = 500 ns;
    # device 1: [100, 600] = 500 ns
    assert r.busy_s == pytest.approx(500e-9)
    assert r.idle_pct == pytest.approx(50.0)
    # fused: (100 + 200) on device 0 and 500 on device 1, averaged
    assert r.kernel_s["fused_rbf"] == pytest.approx(400e-9)
    assert r.kernel_s["fused_nystrom"] == pytest.approx(25e-9)
    assert r.allreduce_s == pytest.approx(100e-9)


def test_idle_gaps_named_by_the_host_span_that_covers_them():
    r = tr.reduce(fake_trace(), 2)
    gaps = dict(r.idle_gaps)
    # device 0 idles in [200,300] (inside eigensolve and the job: the
    # innermost wins), [550,800] (the job covers all of it, eigensolve and
    # assign 100 ns each) and [900,1050] (inside assign and the job)
    assert gaps == pytest.approx({"fit.eigensolve": 100e-9,
                                  "bench.fit_job": 250e-9,
                                  "fit.assign": 150e-9})
    b = r.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    assert b["device_ops"][0][0] == "fused_rbf"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_a_trace_without_a_device_plane_is_an_error():
    with pytest.raises(ValueError, match="no TPU device plane"):
        tr.reduce(NS(planes=[plane("/host:CPU", [])]), 1)


def test_the_recorded_v5e_trace():
    r = tr.reduce(tr.load(RECORDED), 1)
    assert r.window_s == pytest.approx(3.9256, abs=1e-4)
    assert r.busy_s == pytest.approx(0.05885, abs=1e-5)
    # the Pallas calls: one degree pass and 4 block passes, 24 serve steps
    assert r.kernel_s["fused_rbf"] == pytest.approx(0.025256, abs=1e-6)
    assert r.kernel_s["fused_nystrom"] == pytest.approx(0.0075825, abs=1e-7)
    assert r.allreduce_s == 0.0
    b = r.breakdown()
    assert b["device_ops"][1] == ["fused_rbf", r.kernel_s["fused_rbf"]]
    # at n=16,384 the passes take milliseconds: the job's eigensolve waits
    # on the host (its callback programs compile in every job)
    assert b["idle_gaps"][0][0] == "fit.eigensolve"
    assert b["idle_gaps"][0][1] > 3.0
