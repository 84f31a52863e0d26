"""Programs the run's set-up did not find in the compile cache
(``repro.compile_cache.stats()`` misses across set-up)."""


def read(ctx):
    return ctx.counters.get("compile_misses")
