"""The entry points' persistent compilation cache: where it goes."""
from pathlib import Path

import jax
import pytest

from repro.compile_cache import enable_compile_cache

CHECKOUT = Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("env", [None, "/some/dir"])
def test_compile_cache_dir(monkeypatch, env):
    """JAX_COMPILATION_CACHE_DIR, where set, is the cache and the code sets
    nothing; otherwise the cache is the fixed <checkout>/.jax_cache."""
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = enable_compile_cache()
        after = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    if env is None:
        assert got == after == str(CHECKOUT / ".jax_cache")
    else:
        assert got == env
        assert after == before
