"""The KDA layers' share of their roofline, in %: the roofline time of
their work in a step over their device time (``kda_ms``).  The roofline
time is the larger of the operations over the bf16 peak and the bytes
over HBM bandwidth (``peaks.json``), both counted from the configuration's
stated sizes by ``reference/kimi_linear.py`` (``kda_step_flops``,
``kda_step_bytes``), as the model requires them, per chip.  Nothing where
``kda_ms`` reads nothing."""
from metrics import kda_ms
from reference import kimi_linear


def read(ctx):
    ms = kda_ms.read(ctx)
    if not ms:
        return None
    s = ctx.cell.config
    tokens = ctx.cell.tokens_per_step / ctx.cell.chips
    t = max(kimi_linear.kda_step_flops(s, tokens) / ctx.peaks["bf16_flops_per_s"],
            kimi_linear.kda_step_bytes(s, tokens) / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * t / (ms / 1e3)
