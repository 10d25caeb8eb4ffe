"""The persistent compilation cache lands in JAX_COMPILATION_CACHE_DIR when
it is set, and in the fixed in-checkout directory otherwise."""
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    prev = {k: getattr(jax.config, k) for k in keys}
    compilation_cache.reset_cache()
    yield
    for k, v in prev.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def _entries(path):
    return sorted(p.name for p in path.iterdir()) if path.exists() else []


def test_cache_lands_in_env_dir(tmp_path, monkeypatch, restore_cache_config):
    cache_dir = tmp_path / "cache"
    monkeypatch.setenv(compile_cache.ENV_VAR, str(cache_dir))
    default_before = _entries(compile_cache.DEFAULT_DIR)
    assert compile_cache.enable_compile_cache() == str(cache_dir)
    assert jax.config.jax_compilation_cache_dir == str(cache_dir)
    # cache every compile, however small, so one tiny jit must write an entry
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
    assert _entries(cache_dir), "no cache entry written to the env directory"
    assert _entries(compile_cache.DEFAULT_DIR) == default_before


def test_cache_defaults_to_fixed_checkout_dir(monkeypatch,
                                              restore_cache_config):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.enable_compile_cache()
    repo_root = Path(__file__).resolve().parents[1]
    assert path == str(repo_root / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    # a fixed name: the same on every call, in every process
    assert compile_cache.enable_compile_cache() == path
