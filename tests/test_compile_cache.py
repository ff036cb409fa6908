"""The persistent compilation cache helper: where it puts the cache, and
that it counts what the cache does."""
import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.utils import compile_cache


@pytest.fixture
def restore_jax_cache_config():
    was = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_persistent_cache_min_compile_time_secs)
    listeners = []
    yield listeners
    for fn in listeners:
        jax.monitoring.unregister_event_listener(fn)
    jax.config.update("jax_compilation_cache_dir", was[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", was[1])
    compilation_cache.reset_cache()


def test_env_dir_is_used_and_nothing_else_set(monkeypatch, tmp_path,
                                              restore_jax_cache_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    events = compile_cache.enable_compile_cache()
    restore_jax_cache_config.append(events._on_event)
    assert events.path == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_inside_the_checkout(monkeypatch,
                                                  restore_jax_cache_config):
    import os

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    events = compile_cache.enable_compile_cache()
    restore_jax_cache_config.append(events._on_event)
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert events.path == os.path.join(checkout, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == events.path


def test_second_compile_is_a_counted_cache_hit(monkeypatch, tmp_path,
                                               restore_jax_cache_config):
    def f(x):
        return jnp.cumsum(x * 3 + 1) - 17

    x = jax.ShapeDtypeStruct((37,), jnp.int32)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(compile_cache, "DEFAULT_CACHE_DIR", str(tmp_path))
    events = compile_cache.enable_compile_cache()
    restore_jax_cache_config.append(events._on_event)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compilation_cache.reset_cache()

    jax.jit(f).lower(x).compile()
    assert events.writes == 1 and events.hits == 0
    jax.clear_caches()
    jax.jit(f).lower(x).compile()
    assert events.hits == 1 and events.writes == 1
