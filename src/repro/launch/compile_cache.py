"""Where JAX keeps its persistent compilation cache for this checkout.

Entry points (``chip_smoke.py``, ``repro.launch.serve.main``,
``benchmarks/run.py``) call :func:`configure_compile_cache` once at
start-up; importing this module changes nothing.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this sets no
  other path, so the caller decides where the cache lives.
* Otherwise, on an accelerator, the cache goes to ``<checkout>/.jax_cache``
  (git-ignored).  The path is part of the cache key, so it is fixed: a
  directory named after a temporary name, a pid or the time would never
  hit again.  On the CPU backend nothing is set: reloaded XLA:CPU
  executables log host-feature mismatch errors, and the cache exists for
  the chip's long compiles.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def configure_compile_cache() -> str | None:
    """Point JAX's persistent compilation cache at its directory and
    return that directory (None: no cache set on the CPU backend)."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    import jax
    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
