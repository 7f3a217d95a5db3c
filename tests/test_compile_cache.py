"""JAX's persistent compile cache is kept at one fixed place."""
from pathlib import Path

import jax
import pytest

from repro.launch.compile_cache import use_compile_cache

CHECKOUT = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_cache_defaults_to_the_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = use_compile_cache()
    assert path == str(CHECKOUT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


def test_cache_dir_from_environment_is_left_alone(monkeypatch, tmp_path,
                                                  restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
