"""Where JAX's persistent compilation cache lives.

The cache's path is part of its key, so it has to be the same on every
run: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX
reads that variable itself — nothing is set in code), otherwise
``<checkout>/.jax_cache``.  Entry points (``chip_smoke.py``, ``bench.py``,
``tests/conftest.py``) call :func:`enable_compile_cache` before their
first compile; the library never places a cache on its own.
"""
import os

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Place the cache and return its directory."""
    import jax
    if not os.environ.get(CACHE_DIR_ENV):
        jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return jax.config.jax_compilation_cache_dir
