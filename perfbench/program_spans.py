"""The program's own spans (``repro.obs``) of the window's jobs, for the
per-layer readers that read them.

The window's jobs are the last ``n`` finished ``job`` spans that carry no
``error``, ``n`` being the harness's count of jobs completed in the window
(its ``assign_s`` readings): set-up's job comes before them, and a failed
job ends its span with an ``error``.  A program without ``job`` spans
gives no jobs, and the readers then report nothing.
"""
from __future__ import annotations


def window_jobs(ctx) -> list:
    """[(job span, {span name: seconds, summed over the job's descendant
    spans of that name})] for the window's jobs, oldest first."""
    n = len(ctx.counters.get("assign_s", []))
    if not n:
        return []
    from repro import obs
    spans = obs.spans()
    jobs = [s for s in spans if s.name == "job" and "error" not in s.attrs]
    jobs = jobs[-n:]
    if not jobs:
        return []
    parent = {s.sid: s.parent for s in spans}
    job_ids = {j.sid: {} for j in jobs}
    for s in spans:
        p = parent.get(s.sid)
        while p is not None and p not in job_ids:
            p = parent.get(p)
        if p is not None:
            secs = job_ids[p]
            secs[s.name] = secs.get(s.name, 0.0) + s.duration_s
    return [(j, job_ids[j.sid]) for j in jobs]


def mean(values) -> float | None:
    values = [v for v in values if v is not None]
    return sum(values) / len(values) if values else None
