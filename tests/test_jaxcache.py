"""The compile cache is placeable from outside: JAX_COMPILATION_CACHE_DIR
wins; otherwise the fixed <checkout>/.jax_cache."""

import pathlib

import jax
import pytest

from geomesa_tpu.utils import jaxcache

CHECKOUT_CACHE = str(pathlib.Path(__file__).resolve().parents[1]
                     / ".jax_cache")


@pytest.mark.parametrize("env", ["/elsewhere/jax-cache", None])
def test_cache_dir(monkeypatch, env):
    before = jax.config.jax_compilation_cache_dir
    try:
        if env is None:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        else:
            # JAX reads the variable when it starts; mirror that here
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
            jax.config.update("jax_compilation_cache_dir", env)
        jaxcache.ensure_compile_cache()
        assert jax.config.jax_compilation_cache_dir == (env or
                                                        CHECKOUT_CACHE)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
