"""Backward time per traced step, in ms: device self time, on the busiest
device, of the ops under JAX's ``transpose(`` name stack, the forward ops
that remat recomputes there included (step program layer; ``scopes.py``)."""
import scopes


def read(ctx):
    return scopes.per_step_ms(ctx, "backward")
