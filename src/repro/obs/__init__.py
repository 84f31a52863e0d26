"""``repro.obs`` — the unified observability layer.

Zero-required-dependency tracing spans, a process-wide metrics registry,
and Chrome-trace export, wired through every subsystem (estimator fit
phases, engine map/shuffle/reduce, the batched predict service, the
autotuner).  See API.md "Observability".

    from repro import obs

    with obs.span("fit.affinity"): ...          # hierarchical, thread-safe
    obs.counter("engine.map_tasks").inc()
    obs.histogram("serve.request_ms").observe(3.2)
    obs.absorb_stats("engine.store", store.stats)   # ad-hoc dicts -> metrics
    obs.export_trace("trace.json")              # chrome://tracing
    obs.metrics.to_json("metrics.json")

JAX's trace, lower and compile events are charged to the spans open when
they happen (``repro.obs.jit``): ``jit.*{span=...}`` counters, and
``jit_s`` / ``jit_programs`` attrs on each open span.

``obs.set_enabled(False)`` turns spans, compile accounting and stat
absorption into no-ops.
"""
from __future__ import annotations

from repro.obs import jit as _jit
from repro.obs.metrics import (DEFAULT_BUCKETS_MS, Counter, Gauge, Histogram,
                               MetricsRegistry, nearest_rank)
from repro.obs.report import fit_obs, phase_summary, write_artifacts
from repro.obs.trace import SPAN_RING, Span, Tracer

# the process-wide instances every subsystem shares
metrics = MetricsRegistry()
tracer = Tracer(on_drop=lambda: metrics.counter("obs.spans_dropped").inc())
_jit.install(tracer, metrics)

# bound module-level helpers (the common call sites)
span = tracer.span
traced = tracer.traced
current_span = tracer.current
spans = tracer.spans
export_trace = tracer.export
counter = metrics.counter
gauge = metrics.gauge
histogram = metrics.histogram
absorb_stats = metrics.absorb_stats
snapshot = metrics.snapshot


def set_enabled(on: bool) -> None:
    """Toggle span recording, compile accounting AND stat absorption
    process-wide (direct metric objects already held by callers keep
    working either way)."""
    tracer.enabled = on
    metrics.enabled = on


def enabled() -> bool:
    return tracer.enabled


def reset() -> None:
    """Clear all recorded spans and metrics (tests; between CLI runs)."""
    tracer.reset()
    metrics.reset()


__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "SPAN_RING", "Span",
    "Tracer", "DEFAULT_BUCKETS_MS", "absorb_stats", "counter",
    "current_span", "enabled", "export_trace", "fit_obs", "gauge",
    "histogram", "metrics",
    "nearest_rank", "phase_summary", "reset", "set_enabled", "snapshot",
    "span", "spans", "traced", "tracer", "write_artifacts",
]
