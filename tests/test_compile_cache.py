"""The persistent compilation cache lives at one fixed place: where
``JAX_COMPILATION_CACHE_DIR`` says when it is set, else ``.jax_cache`` at
the root of the checkout."""
import os

import jax
import pytest

from repro.launch import compile_cache


_KEYS = ("jax_compilation_cache_dir",
         "jax_compilation_cache_include_metadata_in_key",
         "jax_hlo_source_file_canonicalization_regex",
         "jax_persistent_cache_min_compile_time_secs",
         "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture
def cache_dir_config():
    before = {k: getattr(jax.config, k) for k in _KEYS}
    yield
    for k, v in before.items():
        jax.config.update(k, v)


def _entries_of(programs, cache):
    """Cache entries written while compiling each of ``programs`` (no-arg
    callables that configure and return a jitted function) into
    ``cache``."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        for program in programs:
            step = program()
            jax.config.update("jax_compilation_cache_dir", str(cache))
            jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
            jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
            cc.reset_cache()
            step(1.0).block_until_ready()
    finally:
        cc.reset_cache()
    return [f for f in os.listdir(cache) if f.endswith("-cache")]


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


def _twin(scope):
    """A program named ``step`` whose one difference from its twin is the
    named scope around it."""
    def program():
        compile_cache.configure_compile_cache()

        def step(x):
            with jax.named_scope(scope):
                return x * 2.0 + 1.0
        return jax.jit(step)
    return program


def test_the_cache_key_keeps_the_op_names(cache_dir_config, tmp_path):
    """Two programs that differ only in their metadata get two cache
    entries, so a trace never shows the op names of an older program."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", False)
    assert len(_entries_of([_twin("attention"), _twin("adam")],
                           tmp_path)) == 2


def test_a_checkout_elsewhere_hits_the_cache(cache_dir_config, tmp_path,
                                             monkeypatch):
    """The same program in two checkouts at different paths is one cache
    entry: source files enter the key relative to the checkout."""
    import importlib.util
    src = ("import jax\n\n\ndef step(x):\n"
           "    with jax.named_scope('attention'):\n"
           "        return x * 2.0 + 1.0\n")

    def checkout(name):
        root = tmp_path / name
        root.mkdir()
        (root / "prog.py").write_text(src)

        def program():
            monkeypatch.setattr(compile_cache, "CHECKOUT_ROOT", str(root))
            compile_cache.configure_compile_cache()
            spec = importlib.util.spec_from_file_location(
                f"prog_{name}", root / "prog.py")
            module = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(module)
            return jax.jit(module.step)
        return program

    cache = tmp_path / "cache"
    assert len(_entries_of([checkout("one"), checkout("two")], cache)) == 1
