"""Attention time per traced step, in ms: device self time, on the busiest
device, of the ops under the blocks' ``attn`` scope (the mixer and its
norm), forward and backward (``scopes.py``)."""
import scopes


def read(ctx):
    return scopes.per_step_ms(ctx, "attn")
