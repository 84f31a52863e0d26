"""Mean seconds of the program's ``fit.eigensolve`` span per job."""


def read(ctx):
    v = [x for x in ctx.counters.get("eigensolve_s", []) if x is not None]
    return sum(v) / len(v) if v else None
