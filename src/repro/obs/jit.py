"""Compile accounting: JAX's trace, lower and compile events, charged to
the spans open on the thread that caused them.

JAX reports each jaxpr trace, each lowering to an MLIR module and each
backend compile (in JAX 0.9 a compile, or a load from the persistent
cache) as a monitoring event on the thread that dispatched the call.
:class:`JitAccounting` listens to those and to the persistent cache's hit
and miss events, and books each one twice:

* in the registry, labeled by the innermost open span (``span=none``
  outside any span): ``jit.traces``, ``jit.trace_s``, ``jit.lower_s``,
  ``jit.programs`` (compile-or-load events), ``jit.compile_s``,
  ``jit.cache_hits`` and ``jit.cache_misses`` (JAX's name for a program
  written to the cache after a lookup found nothing);
* on every span open on the thread, inclusively: ``attrs["jit_s"]``
  (trace + lower + compile seconds) and ``attrs["jit_programs"]``; the
  innermost span also lists, in ``attrs["jit_funs"]``, the first
  :data:`FUNS_KEPT` functions that compiled under it.

A jaxpr trace that runs inside another one (a jitted helper traced while
its caller is) is reported, and counted, at each level, so ``jit.trace_s``
can exceed the wall it covers; lowering and compiling are not nested.
The listeners run only when JAX compiles, never per operation, and do
nothing while the tracer is disabled.
"""
from __future__ import annotations

from typing import Any

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_MISS = "/jax/compilation_cache/cache_misses"

_SECONDS = {TRACE: "jit.trace_s", LOWER: "jit.lower_s",
            COMPILE: "jit.compile_s"}
_COUNTS = {CACHE_HIT: "jit.cache_hits", CACHE_MISS: "jit.cache_misses"}
FUNS_KEPT = 8


class JitAccounting:
    """The listeners that charge JAX's compile events to ``tracer``'s open
    spans and to ``registry``'s ``jit.*`` counters."""

    def __init__(self, tracer, registry):
        self.tracer = tracer
        self.registry = registry

    def _where(self):
        stack = self.tracer.open_spans()
        return stack, stack[-1].name if stack else "none"

    def on_duration(self, event: str, secs: float, fun_name: Any = "",
                    **_kw) -> None:
        name = _SECONDS.get(event)
        if name is None or not self.tracer.enabled:
            return
        stack, label = self._where()
        reg = self.registry
        reg.counter(name, span=label).inc(secs)
        if event == TRACE:
            reg.counter("jit.traces", span=label).inc()
        elif event == COMPILE:
            reg.counter("jit.programs", span=label).inc()
        for sp in stack:
            a = sp.attrs
            a["jit_s"] = a.get("jit_s", 0.0) + secs
            if event == COMPILE:
                a["jit_programs"] = a.get("jit_programs", 0) + 1
        if event == COMPILE and stack:
            funs = stack[-1].attrs.setdefault("jit_funs", [])
            if len(funs) < FUNS_KEPT:
                funs.append(str(fun_name))

    def on_event(self, event: str, **_kw) -> None:
        name = _COUNTS.get(event)
        if name is None or not self.tracer.enabled:
            return
        self.registry.counter(name, span=self._where()[1]).inc()


def install(tracer, registry) -> None:
    """Register one :class:`JitAccounting`'s listeners with JAX.  Without
    JAX there is nothing to count, and nothing is registered."""
    try:
        import jax.monitoring as mon
    except ImportError:      # pragma: no cover - jax absent
        return
    acc = JitAccounting(tracer, registry)
    mon.register_event_duration_secs_listener(acc.on_duration)
    mon.register_event_listener(acc.on_event)
