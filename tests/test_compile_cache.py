"""utils/compile_cache.py: the one place the persistent cache is placed.

conftest.py already called the helper for this session, so the config holds
``<checkout>/.jax_cache`` on entry; each test restores what it changes.
"""

import os

import jax
import pytest

from fedml_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_dir_config():
    """Yield a setter for jax's cache-dir option; restore it afterwards."""
    before = jax.config.jax_compilation_cache_dir
    yield lambda v: jax.config.update("jax_compilation_cache_dir", v)
    jax.config.update("jax_compilation_cache_dir", before)


def test_default_is_the_fixed_checkout_path(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    cache_dir_config(None)
    assert compile_cache.enable_compile_cache() == \
        os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == \
        os.path.join(REPO, ".jax_cache")


def test_env_variable_wins_nothing_set_in_code(monkeypatch, cache_dir_config,
                                               tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, JAX reads the variable itself at
    import; the helper must leave the option exactly as it found it."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    cache_dir_config("/what/jax/read/from/the/environment")
    assert compile_cache.enable_compile_cache() == \
        "/what/jax/read/from/the/environment"
    assert jax.config.jax_compilation_cache_dir == \
        "/what/jax/read/from/the/environment"


def test_thresholds_set_in_one_place():
    compile_cache.enable_compile_cache()
    assert jax.config.jax_persistent_cache_min_compile_time_secs == \
        compile_cache.MIN_COMPILE_SECS
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == \
        compile_cache.MIN_ENTRY_BYTES
