"""Shared artifact locations and the compile-cache placement.

Import-safe by construction: ``dryrun.py`` must set XLA_FLAGS (512 fake host
devices) before jax initializes, so nothing that merely needs these paths may
import ``dryrun`` — reporting tools importing ``dryrun.ARTIFACTS`` used to
silently drag a 512-device CPU backend into training processes.  Nothing
here imports jax at module level either.
"""
from __future__ import annotations

import os

_ROOT = os.path.normpath(os.path.join(os.path.dirname(__file__), "..", "..",
                                      ".."))

ARTIFACTS = os.path.join(_ROOT, "artifacts", "dryrun")
COMM_PLANS = os.path.join(_ROOT, "artifacts", "comm_plans")
EXPERIMENTS = os.path.join(_ROOT, "EXPERIMENTS.md")
COMPILE_CACHE = os.path.join(_ROOT, ".jax_cache")
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def use_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here.  Otherwise, on an accelerator, the cache goes to
    the fixed ``<checkout>/.jax_cache``: the path is part of what a later
    run must find, so it never holds a temp name, a pid or a time.  On the
    CPU backend nothing is set and ``""`` returned: its compiles are cheap,
    and XLA:CPU entries reload with machine-feature errors in the log.
    Called by the entry points (``train.main``, ``serve.main``,
    ``chip_smoke.py``), never at import."""
    env = os.environ.get(CACHE_ENV, "").strip()
    if env:
        return env
    import jax
    if jax.default_backend() == "cpu":
        return ""
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE)
    return COMPILE_CACHE
