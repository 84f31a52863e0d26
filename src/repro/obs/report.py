"""Surfacing helpers: the ``info_["obs"]`` schema, the ``[obs]`` one-line
phase summary, and the CLI artifact writer.

``fit_obs`` turns the estimator's phase spans into the stable dict every
fit publishes (see API.md "Observability")::

    {"wall_s": 1.23,
     "coverage": 0.98,                     # phase wall / total wall
     "phases": {"affinity":   {"wall_s": 0.45, "frac": 0.37,
                               "jit_s": 0.0, "jit_programs": 0},
                "eigensolve": {"wall_s": 0.61, "frac": 0.50,
                               "jit_s": 0.09, "jit_programs": 1},
                "assign":     {"wall_s": 0.12, "frac": 0.10,
                               "jit_s": 0.14, "jit_programs": 2}},
     "counters": {"matrix_passes": 17, ...}}

``phase_summary`` renders that dict as the end-of-run ``[obs]`` line the
CLIs print (and the CI obs-smoke job greps).
"""
from __future__ import annotations

from typing import Any, Dict, Optional


def fit_obs(total_span, phase_spans: Dict[str, Any],
            counters: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Assemble ``info_["obs"]`` from one finished parent span and its
    finished phase spans.  Coverage is the fraction of the parent's wall
    the (non-overlapping) phases account for — the acceptance gate is
    >= 0.95 on every fit path.  ``jit_s`` / ``jit_programs`` are the
    seconds JAX spent tracing, lowering and compiling or loading inside
    each phase, and the programs it compiled or loaded there
    (``repro.obs.jit``)."""
    total = max(total_span.duration_s, 1e-12)
    phases = {}
    covered = 0.0
    for name, sp in phase_spans.items():
        d = sp.duration_s
        covered += d
        phases[name] = {"wall_s": round(d, 6), "frac": round(d / total, 4),
                        "jit_s": round(sp.attrs.get("jit_s", 0.0), 6),
                        "jit_programs": sp.attrs.get("jit_programs", 0)}
    out: Dict[str, Any] = {
        "wall_s": round(total_span.duration_s, 6),
        "coverage": round(min(covered / total, 1.0), 4),
        "phases": phases,
    }
    if counters:
        out["counters"] = {k: v for k, v in counters.items()
                           if isinstance(v, (int, float))
                           and not isinstance(v, bool)}
    return out


def phase_summary(obs_info: Dict[str, Any], tag: str = "fit") -> str:
    """One ``[obs]`` line: total wall, per-phase wall + share, coverage."""
    parts = [f"[obs] {tag}={obs_info.get('wall_s', 0.0):.3f}s"]
    for name, ph in obs_info.get("phases", {}).items():
        parts.append(f"{name}={ph['wall_s']:.3f}s({ph['frac']:.0%})")
    parts.append(f"coverage={obs_info.get('coverage', 0.0):.0%}")
    counters = obs_info.get("counters") or {}
    if "matrix_passes" in counters:
        parts.append(f"matrix_passes={counters['matrix_passes']}")
    return " ".join(parts)


def write_artifacts(trace_out: Optional[str] = None,
                    metrics_out: Optional[str] = None,
                    tracer=None, registry=None) -> None:
    """CLI tail shared by ``spectral_job`` and ``cluster_serve``: export
    the Chrome trace and/or the metrics snapshot when the flags were
    given, printing where each landed."""
    from repro.obs import metrics as default_metrics
    from repro.obs import tracer as default_tracer

    if trace_out:
        (tracer or default_tracer).export(trace_out)
        print(f"[obs] trace -> {trace_out} (open in chrome://tracing)")
    if metrics_out:
        (registry or default_metrics).to_json(metrics_out)
        print(f"[obs] metrics -> {metrics_out}")
