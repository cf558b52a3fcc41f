"""`runtime.enable_compile_cache`: the environment's directory when
JAX_COMPILATION_CACHE_DIR is set (and nothing set in code), otherwise one
fixed directory inside the checkout."""
from pathlib import Path

import jax
import pytest

from repro.runtime import enable_compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("env_set", [False, True])
def test_enable_compile_cache(env_set, monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    if env_set:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        path = enable_compile_cache()
        now = jax.config.jax_compilation_cache_dir
    finally:  # the tests never run with the cache on
        jax.config.update("jax_compilation_cache_dir", before)
    if env_set:
        assert path == str(tmp_path)
        assert now == before, "the env-var branch must set no directory"
    else:
        assert path == str(REPO / ".jax_cache")
        assert now == path
