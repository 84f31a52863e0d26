"""The work of one pass of the fused RBF kernel, counted on the
benchmark's side from the cell's shapes, and the peaks of the chips it
runs on: what ``fused_roofline_pct`` divides by.

One pass of width b over n rows of width d computes the Gram tiles,
2 n^2 d flop, and the RBF tile times V product, 2 n^2 b flop (the exp
and the distance terms, O(n^2), are left out).  Its compulsory HBM
traffic is the rows once at their itemsize, V in and the output out in
float32.  The peaks are Google Cloud's published figures for one chip
("TPU v5e": 197e12 bf16 flop/s, 819e9 HBM bytes/s), keyed by JAX's
``device_kind``; a chip not in the table is an error.
"""
from __future__ import annotations

PEAKS = {"TPU v5 lite": {"flops": 197e12, "bytes": 819e9}}


def pass_flops(n: int, d: int, b: int) -> float:
    return 2.0 * n * n * (d + b)


def pass_bytes(n: int, d: int, b: int, itemsize: int) -> float:
    return float(n * d * itemsize + 2 * n * b * 4)


def least_seconds(widths: dict, n: int, d: int, itemsize: int,
                  device_kind: str) -> float:
    """The least time the chip could take for the passes ``{width:
    count}``: per pass, the larger of its flop over peak flop/s and its
    compulsory bytes over peak bytes/s."""
    peak = PEAKS[device_kind]
    return sum(c * max(pass_flops(n, d, b) / peak["flops"],
                       pass_bytes(n, d, b, itemsize) / peak["bytes"])
               for b, c in widths.items())
