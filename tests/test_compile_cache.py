"""Compilation-cache placement of the entry points."""
import pathlib

import jax

from repro.launch import compile_cache


def test_env_dir_owns_the_cache(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # nothing overridden


def test_default_dir_is_fixed_in_the_checkout(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    try:
        path = compile_cache.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    repo = pathlib.Path(__file__).resolve().parent.parent
    assert pathlib.Path(path) == repo / ".jax_cache"
    assert ".jax_cache/" in (repo / ".gitignore").read_text().split()
    # the same path on every call: no pid, time or temporary name in it
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    try:
        assert compile_cache.enable_compile_cache() == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
