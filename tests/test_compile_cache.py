"""Where the entry points put JAX's persistent compilation cache."""
import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def cache_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_dir_is_used_and_nothing_is_set(monkeypatch, cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_accelerator_default_is_the_fixed_checkout_dir(monkeypatch,
                                                       cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    got = compile_cache.enable_compile_cache()
    assert got == str(compile_cache.DEFAULT_DIR)
    assert jax.config.jax_compilation_cache_dir == got
    assert compile_cache.DEFAULT_DIR.parent.joinpath(
        "chip_smoke.py").exists()          # the checkout root
    assert compile_cache.enable_compile_cache() == got   # stable path


def test_cpu_keeps_no_default_cache(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == ""
    assert jax.config.jax_compilation_cache_dir == before
