"""Seconds the session spent compiling (or loading from the persistent
cache) its step programs by the end of set-up: ``TrainSession.compile_s``,
from JAX's backend-compile events during the session's own calls.  The
window compiles nothing, so it reads the same after the traced steps."""


def read(ctx):
    return getattr(ctx.session, "compile_s", None)
