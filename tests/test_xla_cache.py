"""JAX's persistent compilation cache is placed from outside
(``$JAX_COMPILATION_CACHE_DIR``) or at one fixed directory in the
checkout -- never at a path that moves between runs."""
import os

import jax
import jax.numpy as jnp
import pytest

from conftest import REPO
from repro.persist import xla_cache as XC

_KEYS = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
         "jax_persistent_cache_min_compile_time_secs")


@pytest.fixture
def jax_cache_config():
    """Restore JAX's cache configuration after the test."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = {k: getattr(jax.config, k) for k in _KEYS}
    cc.reset_cache()
    yield
    for k, v in before.items():
        jax.config.update(k, v)
    cc.reset_cache()


def test_env_dir_is_used_as_given(monkeypatch, tmp_path, jax_cache_config):
    where = str(tmp_path / "jax-cache")
    monkeypatch.setenv(XC.ENV, where)
    assert XC.enable_jax_compile_cache() == where
    assert jax.config.jax_compilation_cache_dir == where
    # a compile lands there
    jax.jit(lambda x: x * 3 + 1).lower(
        jnp.ones((7, 5))).compile()
    assert os.listdir(where)


def test_unset_env_resolves_to_fixed_repo_dir(monkeypatch,
                                              jax_cache_config):
    monkeypatch.delenv(XC.ENV, raising=False)
    first = XC.jax_compile_cache_dir()
    assert first == os.path.join(REPO, ".jax_cache")
    assert XC.jax_compile_cache_dir() == first
    assert XC.enable_jax_compile_cache() == first
    assert jax.config.jax_compilation_cache_dir == first
