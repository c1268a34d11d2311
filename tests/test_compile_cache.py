"""The entry points' compile cache goes where it is told, and only there."""
import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache as cc

from repro import compile_cache


@pytest.fixture
def fresh_cache(monkeypatch, tmp_path):
    """Cache every compile, and restore the process's cache settings."""
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    prev = {k: getattr(jax.config, k) for k in keys}
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(compile_cache, "DEFAULT_DIR", tmp_path / "default")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cc.reset_cache()
    yield tmp_path
    for k, v in prev.items():
        jax.config.update(k, v)
    cc.reset_cache()


def _compile_something(n):
    jax.jit(lambda x: jnp.cos(x) * n)(jnp.ones(n)).block_until_ready()


@pytest.mark.parametrize("from_env", [False, True])
def test_cache_files_land_in_one_directory(fresh_cache, monkeypatch,
                                           from_env):
    default = fresh_cache / "default"
    env_dir = fresh_cache / "env"
    if from_env:
        # JAX reads the variable when it is imported; mirror that here
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(env_dir))
        jax.config.update("jax_compilation_cache_dir", str(env_dir))
    used = compile_cache.enable_compile_cache()
    assert used == str(env_dir if from_env else default)
    assert jax.config.jax_compilation_cache_dir == used
    _compile_something(7 + from_env)
    want, other = (env_dir, default) if from_env else (default, env_dir)
    assert any(want.iterdir())
    assert not other.exists()
