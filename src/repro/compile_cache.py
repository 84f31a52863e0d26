"""JAX's persistent compilation cache for the entry points.

Every entry point (``spectral_job.main``, ``cluster_serve.main``,
``benchmarks/run.py``, ``chip_smoke.py``) calls :func:`enable` before its
first compile, so that a later run of the same checkout loads programs
that an earlier run compiled.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads the directory
itself and this module does not set one.  Otherwise the cache lives in
``<repo>/.jax_cache``: a fixed path inside the checkout, listed in
``.gitignore``.  The path is never built from a temporary name, a pid or
the time: a cache that moves between runs is never hit.

Either way every program is stored, however quickly it compiled and
however small it is.  JAX's defaults skip programs that compile in under
a second, and most of this repo's are such: a later run would compile
them all again (a second run on one TPU v5e found 20 of 386 programs
cached).

:func:`stats` counts this process's cache lookups from JAX's own
monitoring events: ``hits``, ``misses`` (lookups that found nothing) and
``writes`` (entries stored).
"""
from __future__ import annotations

import os

import jax

from repro import obs

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")

_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "writes",  # JAX's name for a store
}
_listening = False


def _on_event(event: str, **_kw) -> None:
    name = _EVENTS.get(event)
    if name is not None:
        obs.counter(f"compile_cache.{name}").inc()


def enable() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    start counting lookups; returns the directory.  Call it before the
    process compiles anything: JAX opens the cache at its first compile."""
    global _listening
    if not _listening:
        jax.monitoring.register_event_listener(_on_event)
        _listening = True
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # -1: no size floor (0 would let JAX pick one for the filesystem)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    path = os.environ.get(ENV)
    if not path:
        path = REPO_CACHE
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def stats() -> dict:
    """This process's cache lookups since :func:`enable`."""
    requests, hits, writes = (
        int(obs.counter(f"compile_cache.{k}").value)
        for k in ("requests", "hits", "writes"))
    return {"hits": hits, "misses": requests - hits, "writes": writes}
