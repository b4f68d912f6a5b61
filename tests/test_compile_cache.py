"""Where the persistent compilation cache goes (``repro.launch.compile_cache``).

The entry points call ``configure_compile_cache`` at start-up; the caller
places the cache through ``JAX_COMPILATION_CACHE_DIR``, and otherwise it
lives at one fixed, git-ignored path inside the checkout.
"""
import os

import jax

from repro.launch import compile_cache

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_env_var_is_left_to_jax(monkeypatch):
    monkeypatch.setenv(compile_cache.ENV_VAR, "/elsewhere/jax-cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.configure_compile_cache() == "/elsewhere/jax-cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_inside_the_checkout_and_ignored():
    assert compile_cache.DEFAULT_DIR == os.path.join(REPO_ROOT, ".jax_cache")
    with open(os.path.join(REPO_ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_cpu_backend_sets_no_cache(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.configure_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before
