"""KDA time per traced step, in ms: device self time, on the busiest
device, of the ops with ``kda`` among their scope names (the block's
``attn/kda`` scope: the mixer's norm, projections, convolution, gates,
chunked recurrence and output), forward and backward.  Nothing where the
program names no such scope."""
import scopes
import tracefile as tr

_LAST: list = [None, None]       # (trace, its reading): each trace read once


def _kda_ns(ctx):
    found = scopes.program_scopes(ctx.session)
    if found is None:
        return 0
    names, _ = found
    lo, hi = tr.window(ctx.trace)
    plane = tr.busiest(ctx.trace)
    inside = [(n, max(s, lo), min(e, hi)) for n, s, e in ctx.trace.devices[plane]
              if e > lo and s < hi]
    total = 0
    for name, t in tr.self_times(inside):
        if name.startswith(tr.KERNEL_PREFIX):
            name = name[len(tr.KERNEL_PREFIX):]
        if "kda" in scopes.scope_names(names.get(name, "")):
            total += t
    return total


def read(ctx):
    if _LAST[0] is not ctx.trace:
        _LAST[:] = [ctx.trace, _kda_ns(ctx)]
    if not _LAST[1] or not ctx.steps:
        return None
    return _LAST[1] / ctx.steps / 1e6
