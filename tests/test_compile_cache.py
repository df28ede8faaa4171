"""The compile-cache helper: the environment's directory when set, else a
fixed directory in the checkout.  Checks the configured path only."""
import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_set_is_honoured_and_nothing_is_set(monkeypatch, tmp_path,
                                                restore_cache_dir):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_env_unset_uses_fixed_dir_in_checkout(monkeypatch,
                                              restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    got = compile_cache.configure_compile_cache()
    assert got == str(compile_cache.DEFAULT_DIR)
    assert jax.config.jax_compilation_cache_dir == got
    assert compile_cache.DEFAULT_DIR.parent.joinpath("chip_smoke.py") \
        .is_file()
    assert compile_cache.configure_compile_cache() == got  # stable
