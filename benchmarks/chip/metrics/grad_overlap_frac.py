"""Share of the gradient exchange's bytes that the compiled synced step
overlaps with compute: ``hlo_analysis.exchange_overlap`` of the scheduled
text of the session's ``sync`` program (the collectives under
``grad_sync`` whose start and done have compute scheduled between them).
None where the session compiled no synced step, or where the program has
no such reader."""


def read(ctx):
    program = getattr(ctx.session, "programs", {}).get("sync")
    if program is None:
        return None
    try:
        from repro.launch.hlo_analysis import exchange_overlap
    except ImportError:
        return None
    return exchange_overlap(program.as_text())
