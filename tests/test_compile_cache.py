"""The compile-cache helper: one directory, placed from outside or fixed."""
import os

import jax
import pytest

from conftest import REPO

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_dir():
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    cc.reset_cache()


def test_env_var_decides_where_the_cache_goes(
    monkeypatch, tmp_path, restore_cache_dir
):
    where = str(tmp_path / "cache")
    monkeypatch.setenv(compile_cache.ENV_VAR, where)
    assert compile_cache.enable_compile_cache() == where
    assert jax.config.jax_compilation_cache_dir == where


def test_without_env_var_the_cache_is_fixed_in_the_checkout(
    monkeypatch, restore_cache_dir
):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    first = compile_cache.enable_compile_cache()
    second = compile_cache.enable_compile_cache()
    assert first == second == jax.config.jax_compilation_cache_dir
    assert first == os.path.join(REPO, ".jax_cache")
