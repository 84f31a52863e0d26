"""Mean of the eigensolver's ``info_["matrix_passes"]`` per job."""


def read(ctx):
    v = ctx.counters.get("matrix_passes", [])
    return sum(v) / len(v) if v else None
