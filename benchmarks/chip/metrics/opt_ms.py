"""Optimizer time per traced step, in ms: device self time, on the
busiest device, of the ops under the step program's ``optimizer`` scope
(the update and its apply; ``scopes.py``)."""
import scopes


def read(ctx):
    return scopes.per_step_ms(ctx, "optimizer")
