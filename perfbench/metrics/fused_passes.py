"""Mean fused-kernel passes per window job: the program's
``fused.passes{width=...}`` counters, all widths (the degree pass
included), read around each job."""


def read(ctx):
    v = [sum(w.values()) for w in ctx.counters.get("fused_widths", [])
         if w is not None]
    return sum(v) / len(v) if v else None
