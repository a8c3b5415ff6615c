"""Where the command-line entry points keep JAX's compilation cache."""
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache as CC

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_dir_config():
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)
    # drop the cache object JAX may have opened on the test's directory,
    # so later tests in this process compile as if it was never set
    compilation_cache.reset_cache()


def test_env_var_wins_and_nothing_else_is_set(monkeypatch, cache_dir_config):
    monkeypatch.setenv(CC.ENV_VAR, "/some/cache")
    before = jax.config.jax_compilation_cache_dir
    assert CC.enable_compile_cache() == "/some/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_unset_env_uses_fixed_ignored_dir_in_checkout(monkeypatch,
                                                      cache_dir_config):
    monkeypatch.delenv(CC.ENV_VAR, raising=False)
    first = CC.enable_compile_cache()
    assert first == CC.enable_compile_cache() == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
