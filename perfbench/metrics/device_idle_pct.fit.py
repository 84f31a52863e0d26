"""Share of the traced window in which no operation ran on the device
(averaged over the chips), in the fit cells."""


def read(ctx):
    return ctx.trace.idle_pct if ctx.trace else None
