"""Forward time per traced step, in ms: device self time, on the busiest
device, of the ops under the step program's ``forward`` scope and not
under ``transpose(`` (step program layer; ``scopes.py``)."""
import scopes


def read(ctx):
    return scopes.per_step_ms(ctx, "forward")
