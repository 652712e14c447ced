"""Placement of JAX's persistent compilation cache."""

from __future__ import annotations

import pytest

import jax

from repro import compile_cache


@pytest.fixture
def cache_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_dir_wins_and_nothing_is_set(monkeypatch, tmp_path,
                                         cache_config):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == tmp_path
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_fixed_repo_dir(monkeypatch, cache_config):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    got = compile_cache.use_compile_cache()
    assert got == compile_cache.DEFAULT_DIR
    assert got.name == ".jax_cache"
    assert (got.parent / "pyproject.toml").is_file()
    assert jax.config.jax_compilation_cache_dir == str(got)
