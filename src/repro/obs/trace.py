"""Hierarchical tracing spans with a Chrome-trace exporter.

The paper's Hadoop pipeline is legible because every stage is a named job
with counters; this module gives the jax_pallas reproduction the same
property.  A :class:`Span` is one named, timed region::

    from repro import obs

    with obs.span("fit.affinity", backend="fused-rbf") as sp:
        op = build(...)            # sp.duration_s after exit

    @obs.traced("engine.map")
    def run_map_task(...): ...

Spans nest through a thread-local stack (each thread has its own), use
monotonic clocks (``time.perf_counter``), carry arbitrary JSON-able
attributes and the id of the span that was open around them.  The newest
:data:`SPAN_RING` finished spans are kept in a process-wide
:class:`Tracer` and export as Chrome-trace / Perfetto JSON
(``obs.export_trace(path)``) viewable at ``chrome://tracing`` or
https://ui.perfetto.dev.

When ``jax.profiler`` is importable, every span also enters a
``TraceAnnotation`` of the same name, so the region appears on the host
plane of a device profile — purely best-effort, the module has NO
required dependencies beyond the stdlib.  The export's ``metadata`` holds
the tracer's epoch on both ``time.perf_counter_ns()`` and
``time.time_ns()``; a profile's times count from the start of its
trace, so shift the export onto it by one span that both hold.
"""
from __future__ import annotations

import collections
import itertools
import json
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional

try:  # optional pass-through into XLA profiles; never required
    from jax.profiler import TraceAnnotation as _JaxAnnotation
except Exception:  # pragma: no cover - jax absent or too old
    _JaxAnnotation = None

# finished spans kept per tracer, oldest dropped first: a long-running
# process (the predict service spans every step) stays bounded, and a
# 51 s window of back-to-back graph jobs (about 12 spans a job) fits many
# times over
SPAN_RING = 1 << 15


class Span:
    """One named, timed region.  ``t0``/``t1`` are perf_counter seconds
    relative to the owning tracer's epoch; ``t1`` is None while open.
    ``sid`` is unique within the tracer; ``parent`` is the ``sid`` of the
    span open around this one on its thread (None at top level)."""

    __slots__ = ("name", "attrs", "t0", "t1", "tid", "depth", "sid",
                 "parent", "_ann")

    def __init__(self, name: str, attrs: Dict[str, Any], t0: float,
                 tid: int, depth: int, sid: int, parent: Optional[int]):
        self.name = name
        self.attrs = attrs
        self.t0 = t0
        self.t1: Optional[float] = None
        self.tid = tid
        self.depth = depth
        self.sid = sid
        self.parent = parent
        self._ann = None

    @property
    def duration_s(self) -> float:
        return (self.t1 if self.t1 is not None else self.t0) - self.t0

    def set(self, **attrs) -> "Span":
        """Attach attributes to an open (or finished) span."""
        self.attrs.update(attrs)
        return self

    def __repr__(self) -> str:
        state = f"{self.duration_s * 1e3:.2f}ms" if self.t1 is not None \
            else "open"
        return f"Span({self.name!r}, {state}, depth={self.depth})"


class _NullSpan:
    """Returned while tracing is disabled: accepts the same calls, records
    nothing."""

    name = ""
    t0 = t1 = 0.0
    depth = 0
    duration_s = 0.0
    attrs: Dict[str, Any] = {}

    def set(self, **attrs):
        return self

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullSpan()


class _SpanCtx:
    """Context manager binding one Span to one tracer (also what the
    ``traced`` decorator runs around each call)."""

    __slots__ = ("_tracer", "_name", "_attrs", "_span")

    def __init__(self, tracer: "Tracer", name: str, attrs: Dict[str, Any]):
        self._tracer = tracer
        self._name = name
        self._attrs = attrs
        self._span: Optional[Span] = None

    def __enter__(self) -> Span:
        self._span = self._tracer._push(self._name, self._attrs)
        return self._span

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc_type is not None:
            self._span.attrs.setdefault("error", exc_type.__name__)
        self._tracer._pop(self._span)
        return False


class Tracer:
    """Thread-safe collector of the newest :data:`SPAN_RING` finished
    spans.

    One process-wide instance (``repro.obs.tracer``) backs the module-level
    ``span``/``traced``/``export_trace`` helpers; tests may build private
    tracers.  The epoch is captured at construction (and on ``reset``), so
    exported timestamps always start near zero.  ``on_drop`` is called
    once for each finished span the ring pushes out.
    """

    def __init__(self, enabled: bool = True, jax_annotations: bool = True,
                 on_drop: Optional[Callable[[], None]] = None):
        self.enabled = enabled
        self.jax_annotations = jax_annotations
        self.on_drop = on_drop
        self._lock = threading.Lock()
        self._events: "collections.deque[Span]" = collections.deque(
            maxlen=SPAN_RING)
        self._tls = threading.local()
        self._ids = itertools.count(1)
        self._set_epoch()

    def _set_epoch(self) -> None:
        self.epoch_ns = time.perf_counter_ns()
        self.epoch_time_ns = time.time_ns()
        self.epoch = self.epoch_ns * 1e-9

    # -- span lifecycle -----------------------------------------------------

    def _stack(self) -> List[Span]:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def span(self, name: str, **attrs) -> Any:
        """Open a span: ``with tracer.span("fit.affinity") as sp: ...``."""
        if not self.enabled:
            return _NULL
        return _SpanCtx(self, name, attrs)

    def traced(self, name: Optional[str] = None, **attrs) -> Callable:
        """Decorator form: the whole call body becomes one span."""

        def deco(fn: Callable) -> Callable:
            sp_name = name or fn.__qualname__

            def wrapper(*args, **kwargs):
                with self.span(sp_name, **attrs):
                    return fn(*args, **kwargs)

            wrapper.__name__ = fn.__name__
            wrapper.__qualname__ = fn.__qualname__
            wrapper.__doc__ = fn.__doc__
            wrapper.__wrapped__ = fn
            return wrapper

        return deco

    def current(self) -> Optional[Span]:
        """The innermost open span on THIS thread (None at top level)."""
        st = self._stack()
        return st[-1] if st else None

    def open_spans(self) -> List[Span]:
        """The spans open on THIS thread, outermost first (the live stack:
        read it, never change it)."""
        return self._stack()

    def _push(self, name: str, attrs: Dict[str, Any]) -> Span:
        st = self._stack()
        sp = Span(name, attrs, time.perf_counter() - self.epoch,
                  threading.get_ident(), len(st), next(self._ids),
                  st[-1].sid if st else None)
        st.append(sp)
        if self.jax_annotations and _JaxAnnotation is not None:
            try:
                sp._ann = _JaxAnnotation(name)
                sp._ann.__enter__()
            except Exception:   # annotation failure must never break a span
                sp._ann = None
        return sp

    def _pop(self, sp: Span) -> None:
        sp.t1 = time.perf_counter() - self.epoch
        if sp._ann is not None:
            try:
                sp._ann.__exit__(None, None, None)
            except Exception:
                pass
            sp._ann = None
        st = self._stack()
        # exits normally come LIFO; tolerate leaks (an abandoned inner span
        # must not corrupt the outer ones)
        while st and st[-1] is not sp:
            st.pop()
        if st:
            st.pop()
        with self._lock:
            full = len(self._events) == SPAN_RING
            self._events.append(sp)
        if full and self.on_drop is not None:
            self.on_drop()

    # -- inspection / export ------------------------------------------------

    def spans(self, prefix: str = "") -> List[Span]:
        """Finished spans (oldest first), optionally name-filtered."""
        with self._lock:
            return [s for s in self._events if s.name.startswith(prefix)]

    def reset(self) -> None:
        with self._lock:
            self._events.clear()
        self._set_epoch()

    def to_chrome_trace(self) -> Dict[str, Any]:
        """The Chrome-trace JSON object: complete ("ph": "X") events with
        microsecond ``ts``/``dur`` from the epoch, one row per thread.
        Nesting is containment on a tid, which the span stack guarantees;
        each event's ``args`` also give ``span_id`` and ``parent_id``.
        ``metadata`` gives the epoch on the perf_counter and wall clocks."""
        pid = os.getpid()
        events: List[Dict[str, Any]] = []
        tids = {}
        for sp in self.spans():
            # renumber thread ids densely so the viewer rows are stable
            tid = tids.setdefault(sp.tid, len(tids))
            ev = {"name": sp.name, "ph": "X", "pid": pid, "tid": tid,
                  "ts": round(sp.t0 * 1e6, 3),
                  "dur": round(max(sp.duration_s, 0.0) * 1e6, 3),
                  "cat": sp.name.split(".", 1)[0]}
            args = {k: v if isinstance(v, (int, float, bool, str,
                                           type(None)))
                    else str(v) for k, v in sp.attrs.items()}
            args.update(span_id=sp.sid, parent_id=sp.parent)
            ev["args"] = args
            events.append(ev)
        events.sort(key=lambda e: e["ts"])
        meta = [{"name": "process_name", "ph": "M", "pid": pid,
                 "args": {"name": "repro"}}]
        meta += [{"name": "thread_name", "ph": "M", "pid": pid, "tid": t,
                  "args": {"name": "main" if t == 0 else f"thread-{t}"}}
                 for t in sorted(tids.values())]
        return {"traceEvents": meta + events, "displayTimeUnit": "ms",
                "metadata": {"epoch_perf_counter_ns": self.epoch_ns,
                             "epoch_time_ns": self.epoch_time_ns}}

    def export(self, path: str) -> str:
        """Write the Chrome-trace JSON; open it in ``chrome://tracing`` or
        https://ui.perfetto.dev.  Returns ``path``."""
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(self.to_chrome_trace(), f)
        os.replace(tmp, path)
        return path
