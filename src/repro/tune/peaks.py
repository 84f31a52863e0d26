"""The one table of per-chip peaks, and the kernel roofline built on it.

Peaks are keyed by the normalized ``device_kind``
(``repro.tune.cache.device_kind``: "TPU v5 lite" -> "tpu-v5-lite"):

  tpu-v5-lite  Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
               819 GB/s HBM, 1,600 Gbit/s of chip-to-chip interconnect
               (four 50 GB/s links).
  cpu          a conservative host yardstick for interpret-mode runs:
               those numbers are only meaningful relative to each other.

The schedule autotuner and the dry-run (``launch/dryrun.py``) both read
it.  A device that is not in the table is an error, never a default row.
"""
from __future__ import annotations

DEVICE_PEAKS = {
    "tpu-v5-lite": {"flops": 197e12, "bytes": 819e9, "ici_link": 50e9},
    "cpu": {"flops": 5e10, "bytes": 2e10},
}


def device_peaks(kind: str | None = None) -> dict:
    """Peak {flops, bytes}/s for a device kind (default: current backend).
    Raises ``KeyError`` for a kind the table does not list."""
    if kind is None:
        from repro.tune.cache import device_kind
        kind = device_kind()
    try:
        return DEVICE_PEAKS[kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"DEVICE_PEAKS lists {sorted(DEVICE_PEAKS)}") from None


def kernel_roofline(flops: float, bytes_moved: float, wall_s: float,
                    kind: str | None = None) -> dict:
    """Achieved vs peak for one timed kernel call.  Returns ``gflops`` /
    ``gbs`` (achieved rates), ``frac_peak_flops`` / ``frac_peak_bytes``
    (fraction of the device roofline), and the ``dominant`` bottleneck
    (whichever peak-time term is larger)."""
    peaks = device_peaks(kind)
    wall_s = max(float(wall_s), 1e-12)
    t_comp = flops / peaks["flops"]
    t_mem = bytes_moved / peaks["bytes"]
    return {
        "gflops": round(flops / wall_s / 1e9, 2),
        "gbs": round(bytes_moved / wall_s / 1e9, 2),
        "frac_peak_flops": round(flops / wall_s / peaks["flops"], 4),
        "frac_peak_bytes": round(bytes_moved / wall_s / peaks["bytes"], 4),
        "dominant": "compute" if t_comp >= t_mem else "memory",
    }
