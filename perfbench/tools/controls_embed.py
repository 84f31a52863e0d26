#!/usr/bin/env python3
"""Readings that set the embedding cell's limits: whole runs of the cell
through the harness (``perfbench/run.py``'s ``run``), as committed or
under one of its controls, on several seeds in one process.

    python3 perfbench/tools/controls_embed.py \\
        --workload fit.mteb-embed-d4096 --seeds 1,2,3 \\
        --variant tile_bf16 --seconds 10

``--variant`` is ``program`` (the cell as committed), ``tile_bf16``,
``tile_bf16_wide`` or ``gram_drops_last_dtile``
(``perfbench/controls_embed.py``), or ``half_rows``, ``labels_altered``,
``passes1`` or ``passes3`` (``perfbench/controls.py``; the last two are
``products_at(1)`` and ``products_at(3)``).  One JSON line per seed: the
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
VARIANTS = ("program", "tile_bf16", "tile_bf16_wide", "gram_drops_last_dtile",
            "half_rows", "labels_altered", "passes1", "passes3")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variant", default="program", choices=VARIANTS)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from repro import compile_cache

    from perfbench import controls_embed, harness
    from perfbench import run as bench_run
    cell = harness.find_cell(harness.load_benchmark(), args.workload)
    try:
        devices = harness.devices_for(cell.chips)
    except harness.NoDevice as e:
        harness.log(f"controls: {e}")
        return 2
    compile_cache.enable()
    if args.variant == "program":
        broken = contextlib.nullcontext()
    elif args.variant.startswith("passes"):
        broken = controls_embed.products_at(int(args.variant[-1]))
    else:
        broken = getattr(controls_embed, args.variant)()
    with broken:
        for seed in (int(s) for s in args.seeds.split(",")):
            res = bench_run.run(cell, seed, args.seconds, False, devices)
            print(json.dumps({
                "workload": args.workload, "variant": args.variant,
                "seed": seed, "correct": res["correct"],
                "attempted": res["attempted"],
                "metrics": res["metrics"], "checks": res["checks"],
                "readings": res["readings"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
