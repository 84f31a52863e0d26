"""Mean device seconds of one fused pass in the traced window: the
trace's ``fused_rbf`` kernel time over the window jobs' passes (the
program's ``fused.passes`` counters)."""


def read(ctx):
    widths = [w for w in ctx.counters.get("fused_widths", [])
              if w is not None]
    passes = sum(sum(w.values()) for w in widths)
    if not ctx.trace or not passes:
        return None
    secs = ctx.trace.kernel_s.get("fused_rbf")
    return secs / passes if secs else None
