"""Unit coverage for bench/compile_cache.py: where the persistent XLA cache
lives.  With ``JAX_COMPILATION_CACHE_DIR`` set the code sets no directory
(JAX reads the variable) and returns it; unset, the cache is at one fixed
path inside the checkout.  The threshold parameter lands in jax.config either
way."""

import os

import jax
import pytest

from tenzing_tpu.bench.compile_cache import (
    IN_CHECKOUT_CACHE,
    enable_compile_cache,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_jax_cache_config():
    prev_dir = jax.config.jax_compilation_cache_dir
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    jax.config.update("jax_compilation_cache_dir", prev_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", prev_min)


def test_unset_uses_the_fixed_in_checkout_path(monkeypatch,
                                               restore_jax_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = enable_compile_cache()
    assert path == IN_CHECKOUT_CACHE == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 1.0
    # fixed: a second call (another process of the same tree) agrees
    assert enable_compile_cache() == path


def test_env_set_means_code_sets_no_directory(monkeypatch, tmp_path,
                                              restore_jax_cache_config):
    want = str(tmp_path / "cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    sentinel = str(tmp_path / "whatever-jax-already-holds")
    jax.config.update("jax_compilation_cache_dir", sentinel)
    path = enable_compile_cache(min_compile_secs=0.25)
    assert path == want
    # JAX reads the variable itself; this code must not set another
    assert jax.config.jax_compilation_cache_dir == sentinel
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.25
