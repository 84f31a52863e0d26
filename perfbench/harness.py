"""What every cell shares: finding a cell's files by name, the device
check, the benchmark's own spans, and the result line.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``.  Everything
that belongs to one configuration, traffic mix or per-layer metric sits
in a file of its own, found by name:

    perfbench/configs/<config>.json     sizes, assumptions, limits
    perfbench/traffic/<traffic>.json    parameters; ``kind`` names the
                                        general generator in kinds/
    perfbench/metrics/<metric>.py       ``read(ctx)`` -> number or None

so a later cell, configuration, mix or metric is added by adding files
and entries, never by editing one.
"""
from __future__ import annotations

import contextlib
import importlib
import importlib.util
import json
import os
import sys
import time
from dataclasses import dataclass, field
from typing import Any

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class NoDevice(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def find_cell(bench: dict, workload: str, root: str = ROOT) -> Cell:
    """The cell named ``workload``, with its configuration and traffic
    files read and the metrics it reports."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; BENCHMARK.json has "
                       f"{sorted(cells)}")
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(root, cfg_entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "perfbench", "traffic",
                           w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, workload)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, workload)])


def kind_module(traffic: dict):
    """The general generator a traffic file names (``kinds/<kind>.py``)."""
    return importlib.import_module(f"perfbench.kinds.{traffic['kind']}")


def metric_reader(name: str, root: str = ROOT):
    """``read`` of ``metrics/<name>.py``."""
    path = os.path.join(root, "perfbench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "perfbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def devices_for(chips: int):
    """The first ``chips`` TPU devices; NoDevice when JAX has no TPU or
    too few chips.  Never falls back to the CPU."""
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        raise NoDevice(f"JAX finds no TPU (backend {backend!r})")
    devs = jax.devices()
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX finds "
                       f"{len(devs)}")
    return devs[:chips]


@dataclass
class Context:
    """What one run hands the per-layer readers."""
    cell: Cell
    seed: int
    counters: dict = field(default_factory=dict)    # program counters
    trace: Any = None                               # trace_reduce.Reduced

    @staticmethod
    @contextlib.contextmanager
    def span(name: str):
        """A span around a call into one layer, written into the
        profiler's trace (``TraceAnnotation``) when one runs."""
        from jax.profiler import TraceAnnotation
        with TraceAnnotation(name):
            yield


def back_to_back(job, seconds: float, ctx) -> tuple:
    """Run ``job()`` -> (seconds, outputs) one at a time until ``seconds``
    have passed; the job running at the close finishes and counts.
    Records each job's program spans and passes for the per-layer
    readers.  Returns (completed jobs, failed count)."""
    jobs, failed = [], 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        try:
            jobs.append(job())
        except Exception as e:      # a failed job counts, and is shown
            failed += 1
            log(f"job failed: {e!r}")
    for _, out in jobs:
        phases = out["obs"].get("phases", {})
        for name in ("eigensolve", "assign"):
            ctx.counters.setdefault(name + "_s", []).append(
                phases.get(name, {}).get("wall_s"))
        ctx.counters.setdefault("matrix_passes", []).append(out["passes"])
    return jobs, failed


class Compiles:
    """Backend compilations in this process, from JAX's own monitoring
    event: a count and the seconds they took."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, seconds: float, **_kw) -> None:
        if event == self.EVENT:
            self.count += 1
            self.seconds += seconds

    def close(self) -> tuple:
        """Stop counting; the count and the seconds."""
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)
        return self.count, self.seconds


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)
