#!/usr/bin/env python3
"""Readings that set a cell's limits: whole runs of the cell through the
harness (``perfbench/run.py``'s ``run``), as committed or under a
control, on several seeds in one process.

    python3 perfbench/tools/controls.py --workload fit.paper-graph \\
        --seeds 1,2,3 --variant passes3 --seconds 2

``--variant`` is ``program`` (the cell as committed) or ``passes3`` /
``passes1`` (every float32 product of the program's XLA code as three or
one bfloat16 passes; see ``perfbench/controls.py``).  Each seed is one
run: set-up, a window of ``--seconds`` at the cell's own load, and the
cell's comparison against its limits.  One JSON line per seed: the
run's ``correct`` and every reading, compared or not.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variant", default="program",
                    choices=("program", "passes1", "passes3"))
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from repro import compile_cache

    from perfbench import controls, harness
    from perfbench import run as bench_run
    cell = harness.find_cell(harness.load_benchmark(), args.workload)
    try:
        devices = harness.devices_for(cell.chips)
    except harness.NoDevice as e:
        harness.log(f"controls: {e}")
        return 2
    compile_cache.enable()
    broken = contextlib.nullcontext()
    if args.variant.startswith("passes"):
        broken = controls.products_at(int(args.variant[-1]))
    with broken:
        for seed in (int(s) for s in args.seeds.split(",")):
            res = bench_run.run(cell, seed, args.seconds, False, devices)
            print(json.dumps({
                "workload": args.workload, "variant": args.variant,
                "seed": seed, "correct": res["correct"],
                "attempted": res["attempted"], "checks": res["checks"],
                "readings": res["readings"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
