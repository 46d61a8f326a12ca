"""Placement of JAX's persistent compilation cache (``launch/paths.py``)."""
import os

import jax

from repro.launch import paths

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_env_var_wins_and_code_sets_nothing(monkeypatch, tmp_path):
    monkeypatch.setenv(paths.CACHE_ENV, str(tmp_path))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a, **k: calls.append(a))
    assert paths.use_compile_cache() == str(tmp_path)
    assert calls == []


def test_default_is_a_fixed_path_in_the_checkout(monkeypatch):
    monkeypatch.delenv(paths.CACHE_ENV, raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    was = jax.config.jax_compilation_cache_dir
    try:
        got = paths.use_compile_cache()
        assert got == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
        assert paths.use_compile_cache() == got      # no pid, no time
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_cpu_backend_sets_nothing(monkeypatch):
    monkeypatch.delenv(paths.CACHE_ENV, raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    was = jax.config.jax_compilation_cache_dir
    assert paths.use_compile_cache() == ""
    assert jax.config.jax_compilation_cache_dir == was
