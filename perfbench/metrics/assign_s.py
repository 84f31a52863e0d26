"""Mean seconds of the program's ``fit.assign`` span per job."""


def read(ctx):
    v = [x for x in ctx.counters.get("assign_s", []) if x is not None]
    return sum(v) / len(v) if v else None
