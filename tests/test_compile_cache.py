"""The persistent compile cache is placed from outside the program."""
import os

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    compilation_cache.reset_cache()


def test_set_env_dir_is_left_alone(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv(compile_cache.CACHE_ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_inside_the_checkout(monkeypatch,
                                                  restore_cache_dir):
    monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    first = compile_cache.use_compile_cache()
    assert compile_cache.use_compile_cache() == first
    assert jax.config.jax_compilation_cache_dir == first
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert first == os.path.join(root, ".jax_cache")
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
