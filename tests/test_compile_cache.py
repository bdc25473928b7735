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
    names = jax.config.jax_compilation_cache_include_metadata_in_key
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", names)
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


def test_a_renamed_program_is_not_loaded_with_its_old_names(
    monkeypatch, tmp_path, restore_cache_dir
):
    """Two programs that differ only in a named scope get two cache
    entries, so each executable keeps its own op names."""
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    compile_cache.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    def program(scope):
        def f(x):
            with jax.named_scope(scope):
                return x * 2.0 + 1.0
        return jax.jit(f).lower(jax.ShapeDtypeStruct((8,), "float32"))

    for scope in ("first_name", "second_name"):
        text = program(scope).compile().as_text()
        assert scope in text
