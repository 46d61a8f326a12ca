"""Share of the traced window in which the busiest device runs no op."""
import tracefile as tr


def read(ctx):
    lo, hi = tr.window(ctx.trace)
    plane = tr.busiest(ctx.trace)
    return 1.0 - tr.total(tr.busy(ctx.trace, plane)) / (hi - lo)
