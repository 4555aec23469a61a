"""The persistent compilation cache helper the entry points call."""
import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_defaults_to_fixed_checkout_dir(monkeypatch,
                                                      restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.enable_compile_cache()
    assert got == str(compile_cache.CACHE_DIR)
    assert compile_cache.CACHE_DIR.name == ".jax_cache"
    assert (compile_cache.CACHE_DIR.parent / "chip_smoke.py").exists()
    assert jax.config.jax_compilation_cache_dir == got


def test_compile_cache_leaves_env_dir_to_jax(monkeypatch, tmp_path,
                                             restore_cache_dir):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_receives_compiled_programs(monkeypatch, tmp_path,
                                                  restore_cache_dir):
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as cc
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(compile_cache, "CACHE_DIR", tmp_path / "cache")
    prev = jax.config.jax_persistent_cache_min_compile_time_secs
    cc.reset_cache()
    try:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        compile_cache.enable_compile_cache()
        jax.jit(lambda x: x * 3.0 + 1.0)(jnp.arange(7.0)).block_until_ready()
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", prev)
        cc.reset_cache()
    assert any((tmp_path / "cache").iterdir())
