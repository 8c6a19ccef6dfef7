"""The persistent compilation cache lives at one fixed place per checkout."""
from __future__ import annotations

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_dir():
    saved = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", saved)


def test_environment_directory_is_left_to_jax(monkeypatch, tmp_path,
                                              restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_directory_is_fixed_under_the_checkout(monkeypatch,
                                                       restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.enable_compile_cache()
    assert first == compile_cache.enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == first
    assert compile_cache.REPO_CACHE_DIR.parent.joinpath(
        "chip_smoke.py").is_file()
