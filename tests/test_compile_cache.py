"""The entry points' persistent compilation cache directory."""

import os
import tempfile
from pathlib import Path

import jax
import pytest

from repro.launch.compile_cache import CACHE_DIR, enable_compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_config():
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    saved = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in saved.items():
        jax.config.update(n, v)


def test_env_directory_wins(monkeypatch, tmp_path, restore_cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_fixed_directory_in_checkout(monkeypatch, restore_cache_config):
    """Without the variable the path is the checkout's `.jax_cache`, the
    same in every process: no pid, temp name or time in it."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = enable_compile_cache()
    assert path == str(CACHE_DIR) == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert str(os.getpid()) not in path
    assert not path.startswith(tempfile.gettempdir())
