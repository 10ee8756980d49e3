"""The persistent compilation cache lives at one fixed place: where
``JAX_COMPILATION_CACHE_DIR`` says when it is set, else ``.jax_cache`` at
the root of the checkout."""
import os

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def cache_dir_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_variable_wins_and_nothing_else_is_set(monkeypatch,
                                                   cache_dir_config,
                                                   tmp_path):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.configure_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; the program sets no other directory
    assert jax.config.jax_compilation_cache_dir is None


def test_default_is_the_fixed_checkout_directory(monkeypatch,
                                                 cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.configure_compile_cache()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert got == os.path.join(root, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got
    # the same path every time: the cache is keyed on it
    assert compile_cache.configure_compile_cache() == got
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
