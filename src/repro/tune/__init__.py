"""Kernel schedule layer + autotuner (the SYS_ATL/Exo separation).

``Schedule`` is the searchable half of every Pallas kernel: tile sizes,
compute dtype, grid iteration order, accumulator placement and the
interpret flag, with per-kernel legality checks (``KERNELS`` specs).
``ScheduleCache`` persists the best-known schedule per (kernel, shape
bucket, device kind, dtype) as one JSON file; ``autotune`` /
``tune_all`` fill it by timing real kernel calls and scoring them
against the roofline peak model (``repro.tune.peaks``).

Entry points:
  * ``ops.<kernel>(..., schedule=...)`` — None (defaults), "auto"
    (cache), or an explicit Schedule/dict.
  * ``SpectralClustering(schedule="auto")`` — the fused affinity and
    serving paths consult the cache; the chosen schedule lands in
    ``info_``.
  * ``python benchmarks/run.py tune_sweep [--quick]`` — sweep + cache
    write + BENCH_tune.json.
"""
from repro.tune.autotune import autotune, candidates, tune_all
from repro.tune.cache import (ScheduleCache, bucket, cache_key,
                              default_cache, default_cache_path,
                              device_kind)
from repro.tune.schedule import (KERNELS, KernelSpec, Schedule,
                                 ScheduleError, as_schedule, resolve, spec,
                                 validate_spec)

__all__ = [
    "KERNELS", "Schedule", "ScheduleError", "KernelSpec", "as_schedule",
    "resolve", "spec", "validate_spec", "ScheduleCache", "bucket",
    "cache_key", "default_cache", "default_cache_path", "device_kind",
    "autotune", "candidates", "tune_all",
]
