"""The persistent compilation cache lands in one fixed directory: the
one ``JAX_COMPILATION_CACHE_DIR`` names, else the checkout's own
git-ignored directory."""
from pathlib import Path

import jax
import pytest

from repro import compile_cache

_KEYS = ("jax_compilation_cache_dir",
         "jax_persistent_cache_min_compile_time_secs",
         "jax_compilation_cache_max_size")


@pytest.fixture
def restore_config():
    was = {k: getattr(jax.config, k) for k in _KEYS}
    yield
    for k, v in was.items():
        jax.config.update(k, v)


def test_environment_directory_wins(monkeypatch, tmp_path, restore_config):
    was = {k: getattr(jax.config, k) for k in _KEYS[1:]}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    # nothing else is set in code
    assert {k: getattr(jax.config, k) for k in _KEYS[1:]} == was


def test_checkout_directory_is_fixed_and_ignored(monkeypatch,
                                                 restore_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.enable_compile_cache()
    root = Path(__file__).resolve().parents[1]
    assert got == str(root / ".jax_compile_cache")
    assert jax.config.jax_compilation_cache_dir == got
    assert (jax.config.jax_persistent_cache_min_compile_time_secs
            == compile_cache.MIN_COMPILE_SECS)
    # a VGG-16 executor with constant weights (~332 MB) fits
    assert (jax.config.jax_compilation_cache_max_size
            == compile_cache.MAX_CACHE_BYTES > 4 * 332 * 10 ** 6)
    ignored = (root / ".gitignore").read_text().split()
    assert ".jax_compile_cache/" in ignored
