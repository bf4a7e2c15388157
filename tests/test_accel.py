"""Platform resolution and the compile-cache rule (``repro.accel``)."""

import pathlib

import jax
import pytest

from repro import accel


@pytest.fixture
def cache_dir_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_defaults_to_the_checkout(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert accel.enable_compile_cache() == str(accel.CACHE_DIR)
    assert accel.CACHE_DIR.parent == pathlib.Path(__file__).resolve().parents[1]
    assert jax.config.jax_compilation_cache_dir == str(accel.CACHE_DIR)


def test_compile_cache_leaves_the_env_var_to_jax(monkeypatch, cache_dir_config):
    # JAX reads the variable itself; the helper must not override it
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    jax.config.update("jax_compilation_cache_dir", "/elsewhere")
    assert accel.enable_compile_cache() == "/elsewhere"
