"""The whole step's share of the chips' bf16 peak, in %, over the traced
steps: the operations ``flops.py`` counts per step over the mean period
between the first device ops of consecutive steps."""
import tracefile as tr


def read(ctx):
    plane = tr.busiest(ctx.trace)
    starts = tr.step_starts_ns(ctx.trace, plane)
    if len(starts) < 2:
        return None
    period = (starts[-1] - starts[0]) / (len(starts) - 1) / 1e9
    flops = ctx.flops_per_token * ctx.cell.tokens_per_step
    return 100.0 * flops / (period * ctx.cell.chips
                            * ctx.peaks["bf16_flops_per_s"])
