"""The persistent compile cache's placement (repro.launch.compile_cache)."""
from __future__ import annotations

import pathlib

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_env_var_is_left_to_jax(monkeypatch, restore_cache_dir):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, "/elsewhere/cache")
    assert compile_cache.setup_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_fixed_path_in_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    got = compile_cache.setup_compile_cache()
    root = pathlib.Path(__file__).resolve().parents[1]
    assert got == str(root / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got
    assert compile_cache.setup_compile_cache() == got     # no per-call name
