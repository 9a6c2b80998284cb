"""The entry points' compile-cache placement rule."""

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.launch import compile_cache


@pytest.fixture
def cache_dir_setting():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


def test_set_env_var_is_used_and_nothing_is_set(monkeypatch, tmp_path,
                                                 cache_dir_setting):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_unset_env_var_uses_the_fixed_repo_path(monkeypatch,
                                                cache_dir_setting):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    repo = compile_cache.DEFAULT_CACHE_DIR.parent
    assert path == str(repo / ".jax_cache")
    assert (repo / "chip_smoke.py").is_file()
    assert jax.config.jax_compilation_cache_dir == path
