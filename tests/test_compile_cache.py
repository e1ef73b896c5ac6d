"""The entry points' persistent compilation cache lands where a reader of
the checkout expects it: the directory `JAX_COMPILATION_CACHE_DIR` names,
else `<checkout>/.jax_cache` — never a temp, PID or time-derived path."""
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


@pytest.mark.parametrize("env_dir", [None, "named"])
def test_cache_dir(restore_cache_config, monkeypatch, tmp_path, env_dir):
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(Path(__file__).resolve().parents[1] / ".jax_cache")
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
        jax.config.update("jax_compilation_cache_dir", want)  # as at start-up
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert (jax.config.jax_persistent_cache_min_compile_time_secs
            == compile_cache.MIN_COMPILE_SECS)
