"""Gradient-sync time per traced step, in ms: device self time, on the
busiest device, of the ops under the ``grad_sync`` scope of the synced
step (``core/grad_sync.py``: every bucket's packing, compression, exchange
and decoding; ``scopes.py``).  Nothing where the step syncs no gradient."""
import scopes


def read(ctx):
    return scopes.per_step_ms(ctx, "grad_sync") or None
