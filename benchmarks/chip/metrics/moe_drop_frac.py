"""Share of routed token choices the MoE layers dropped to capacity
overflow over the traced steps (the program's own drop counter)."""


def read(ctx):
    routed = ctx.session.routed_tokens
    return ctx.session.dropped_tokens / routed if routed else None
