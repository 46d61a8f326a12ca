"""Exchange time per traced step that the step waits for, in ms: device
self time, on the busiest device, of the collectives (``all-reduce``,
``all-gather``, ``reduce-scatter``, ``collective-permute``,
``all-to-all``, with their ``-start``/``-done``) under the ``grad_sync``
scope.  The chip runs one op at a time, so this is the exchange not
hidden behind compute (``core/collectives``; ``scopes.py``)."""
import scopes


def read(ctx):
    return scopes.per_step_ms(ctx, "grad_sync_collectives") or None
