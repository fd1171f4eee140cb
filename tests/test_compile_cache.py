"""enable_compile_cache: the environment's cache dir wins, else one fixed
directory in the checkout; never on the CPU."""
import jax
import pytest

from repro.launch import compile_cache as cc


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_dir_is_left_to_jax(monkeypatch, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    before = jax.config.jax_compilation_cache_dir
    assert cc.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_checkout_dir_on_an_accelerator(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    got = cc.enable_compile_cache()
    assert got == str(cc.CHECKOUT_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == got
    assert cc.CHECKOUT_CACHE_DIR.name == ".jax_cache"
    assert (cc.CHECKOUT_CACHE_DIR.parent / "pyproject.toml").exists()


def test_no_cache_on_the_cpu(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    before = jax.config.jax_compilation_cache_dir
    assert cc.enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before
