"""The persistent compilation cache: placed from outside, or at a fixed
in-checkout directory that git ignores."""

import os

import jax
import pytest

from repro.launch import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_var_set_touches_no_config(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a, **k: calls.append(a))
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert calls == []


def test_env_var_unset_uses_fixed_checkout_dir(monkeypatch,
                                               restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.use_compile_cache()
    assert path == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    # the same path on every call: nothing derived from pid, time or tmp
    assert compile_cache.use_compile_cache() == path


def test_checkout_cache_dir_is_gitignored():
    with open(os.path.join(REPO, ".gitignore")) as f:
        ignored = {line.strip() for line in f}
    assert ".jax_cache/" in ignored
    assert os.path.basename(compile_cache.CHECKOUT_CACHE_DIR) == ".jax_cache"
