#!/usr/bin/env python3
"""One run of one benchmark cell on the chips of this machine.

    python3 perfbench/run.py --workload fit.blobs262k --seed 7 \\
        --seconds 10 --trace 0

Set-up makes the cell's data from the seed, warms every program the
window runs (the repo's compile cache, ``repro.compile_cache``, keeps them
for the next run of this checkout) and counts as ``setup_s``.  The window
then runs the cell's traffic for ``--seconds``; work that is running when
it closes finishes and counts.  With ``--trace 1`` the window runs under
the JAX profiler and the per-layer metrics are reported instead of the
end-to-end ones.  Once the window has closed and the device's peak memory
is read, what the window produced is compared with the plain reference
(``perfbench/reference.py``); each number compared is printed beside its
limit.  The last stdout line is one JSON object.

Exits 2 without a result when JAX finds no TPU or fewer chips than the
cell asks for, or when the program under test (``src/repro``) is absent.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = _args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from perfbench import harness
    try:
        from repro import compile_cache
    except ImportError as e:
        harness.log(f"perfbench: the program under test is missing ({e})")
        return 2
    cell = harness.find_cell(harness.load_benchmark(), args.workload)
    try:
        devices = harness.devices_for(cell.chips)
    except harness.NoDevice as e:
        harness.log(f"perfbench: {e}; nothing was run")
        return 2
    compile_cache.enable()
    result = run(cell, args.seed, args.seconds, bool(args.trace), devices)
    print(json.dumps(result))
    return 0


def _number(v):
    """A reading as JSON can carry it: None for missing or NaN."""
    return None if v is None or v != v else v


def run(cell, seed: int, seconds: float, trace: bool, devices) -> dict:
    """Set-up, window, peak memory, comparison; the result line's dict."""
    from repro import compile_cache

    from perfbench import harness, trace_reduce
    ctx = harness.Context(cell=cell, seed=seed)
    kind = harness.kind_module(cell.traffic)

    misses0 = compile_cache.stats()["misses"]
    t0 = time.perf_counter()
    state = kind.setup(cell, seed, devices, ctx)
    setup_s = time.perf_counter() - t0
    ctx.counters["compile_misses"] = compile_cache.stats()["misses"] - misses0

    trace_dir = tempfile.mkdtemp(prefix="perfbench-trace-") if trace else None
    if trace:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0       # host spans, not every call
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    compiles = harness.Compiles()
    tw = time.perf_counter()
    with ctx.span(trace_reduce.WINDOW):
        out = kind.window(state, seconds, ctx)
    window_s = time.perf_counter() - tw
    n_comp, s_comp = compiles.close()
    if trace:
        jax.profiler.stop_trace()
        ctx.trace = trace_reduce.reduce_dir(trace_dir, len(devices))
        trace_reduce.remove(trace_dir)

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    kind.release(state)
    gc.collect()
    readings = kind.check(state, ctx) if out["attempted"] else {}
    limits = cell.config["limits"][cell.traffic["kind"]]
    # a reading that is missing (no work done) or not a number fails
    numbers = {name: (_number(readings.get(name)), lim)
               for name, lim in limits.items()}
    correct = all(v is not None and v <= lim for v, lim in numbers.values())

    metrics = {}
    if trace:
        for m in cell.per_layer:
            v = harness.metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = dict(out["e2e"], setup_s=setup_s)
        for m in cell.end_to_end:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result = {"correct": bool(correct), "attempted": int(out["attempted"]),
              "failed": int(out["failed"]), "metrics": metrics,
              "device": device}
    if trace:
        device["busy_s"] = ctx.trace.busy_s
        device["window_s"] = ctx.trace.window_s
        result["breakdown"] = ctx.trace.breakdown()
    harness.log(f"window {window_s:.3f} s, set-up {setup_s:.3f} s, "
                f"{out['attempted']} attempted, {out['failed']} failed, "
                f"{n_comp} programs compiled in the window "
                f"({s_comp:.3f} s)")
    result["readings"] = {name: _number(v) for name, v in readings.items()
                          if name not in limits}
    for name, v in result["readings"].items():
        harness.log(f"reading {name} = {v!r} (not compared)")
    for name, (v, lim) in numbers.items():     # the last lines of stderr
        harness.log(f"check {name} = {v!r} (limit {lim!r})")
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, (v, lim) in numbers.items()}
    return result


if __name__ == "__main__":
    sys.exit(main())
