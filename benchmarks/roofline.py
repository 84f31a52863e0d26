"""Roofline table generator: reads dryrun_results.json, emits the
EXPERIMENTS.md §Roofline markdown table with the three terms, dominant
bottleneck, MODEL_FLOPS/HLO_FLOPs usefulness ratio, and a one-line lever.

    PYTHONPATH=src python -m benchmarks.roofline [--json dryrun_results.json]
"""
from __future__ import annotations

import argparse
import json

from repro.models.config import SHAPES_BY_NAME


def model_flops(rec: dict) -> float:
    """6*N*D (dense) or 6*N_active*D (MoE) for train; 2*N*D for one
    forward-ish serving step (prefill full seq; decode 1 token/seq)."""
    shape = rec["shape"]
    if shape not in SHAPES_BY_NAME:
        return 0.0
    cell = SHAPES_BY_NAME[shape]
    n_active = rec.get("num_active_params", 0)
    if not n_active:
        return 0.0
    if cell.kind == "train":
        tokens = cell.global_batch * cell.seq_len
        return 6.0 * n_active * tokens
    if cell.kind == "prefill":
        tokens = cell.global_batch * cell.seq_len
        return 2.0 * n_active * tokens
    # decode: one new token per sequence
    return 2.0 * n_active * cell.global_batch


def lever(rec: dict) -> str:
    dom = rec["roofline"]["dominant"]
    cb = rec["hlo"]["collective_bytes"]
    if dom == "collective":
        top = max(cb, key=cb.get)
        return f"cut {top} traffic (resharding/overlap)"
    if dom == "memory":
        return "reduce HBM traffic (fusion/bf16/flash-style attention)"
    return "already compute-bound: raise MXU utilization (layout/tiling)"


def table(results: dict, mesh: str = "single") -> str:
    lines = [
        "| arch | shape | chips | t_comp (s) | t_mem (s) | t_coll (s) | "
        "bound | roofline frac | MODEL/HLO flops | lever |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    for key in sorted(results):
        rec = results[key]
        if rec.get("mesh") != mesh or rec.get("tag"):
            continue  # perf-variant records appear in EXPERIMENTS.md §Perf
        if "skipped" in rec:
            lines.append(f"| {rec['arch']} | {rec['shape']} | - | - | - | - | "
                         f"skipped | - | - | {rec['skipped']} |")
            continue
        if "error" in rec:
            lines.append(f"| {rec['arch']} | {rec['shape']} | - | - | - | - | "
                         f"ERROR | - | - | {rec['error'][:60]} |")
            continue
        r = rec["roofline"]
        mf = model_flops(rec)
        hlo_total = rec["hlo"]["flops"] * rec["n_chips"]
        ratio = f"{mf / hlo_total:.2f}" if mf and hlo_total else "-"
        lines.append(
            f"| {rec['arch']} | {rec['shape']} | {rec['n_chips']} "
            f"| {r['t_compute_s']:.3g} | {r['t_memory_s']:.3g} "
            f"| {r['t_collective_s']:.3g} | {r['dominant']} "
            f"| {r['roofline_fraction']:.3f} | {ratio} | {lever(rec)} |")
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default="dryrun_results.json")
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    args = ap.parse_args(argv)
    with open(args.json) as f:
        results = json.load(f)
    print(table(results, args.mesh))


if __name__ == "__main__":
    main()
