"""Share of the fused kernel's roofline in the traced window: the least
time the chip could take for the window jobs' passes (their widths from
the program's ``fused.passes{width=...}`` counters; flop and bytes
counted by ``perfbench/fused_counts.py`` from the cell's n and d) over
the trace's ``fused_rbf`` kernel seconds, in percent."""
import jax

from perfbench import fused_counts


def read(ctx):
    widths = [w for w in ctx.counters.get("fused_widths", [])
              if w is not None]
    secs = ctx.trace.kernel_s.get("fused_rbf") if ctx.trace else None
    if not widths or not secs:
        return None
    total: dict = {}
    for w in widths:
        for b, c in w.items():
            total[b] = total.get(b, 0) + c
    cfg = ctx.cell.config
    itemsize = 2 if cfg["dtype"] == "bfloat16" else 4
    least = fused_counts.least_seconds(total, cfg["n"], cfg["d"], itemsize,
                                       jax.devices()[0].device_kind)
    return 100.0 * least / secs
