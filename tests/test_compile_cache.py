"""The persistent compile cache: placed from outside, else a fixed path."""
import os

import jax

from repro.launch import compile_cache

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _enable_and_restore():
    was = jax.config.jax_compilation_cache_dir
    try:
        return compile_cache.enable(), jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_environment_directory_wins(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.cache_dir() == str(tmp_path)
    assert _enable_and_restore() == (str(tmp_path), str(tmp_path))


def test_default_is_the_same_repo_directory_every_time(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.cache_dir() == want
    assert _enable_and_restore() == (want, want)
    assert _enable_and_restore() == (want, want)
