"""The start-up compile-cache helper of the chip entry points."""
import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_dir():
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)


def test_env_dir_is_honoured_and_nothing_set(monkeypatch, restore_cache_dir,
                                             tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_in_the_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(compile_cache.CHECKOUT / ".jax_cache")
    assert (compile_cache.CHECKOUT / "chip_smoke.py").exists()
    assert jax.config.jax_compilation_cache_dir == path
