"""Placement of JAX's persistent compile cache (stdlib-only).

Every entry point that compiles device programs — the daemon and
cluster CLIs, bench.py, chip_smoke.py, tests/conftest.py, tools/* —
calls ``setup()`` first.  The directory is chosen from OUTSIDE the
program: ``JAX_COMPILATION_CACHE_DIR`` if the environment sets it,
else ``<checkout>/.jax_cache``.  There is no second location: the
path is part of the cache key, so a cache that moves never hits.
"""
import os
import sys

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def setup() -> str:
    """Point JAX at the cache directory and return it.  A directory
    named by the environment is used as is and nothing else is
    created; JAX creates the directory on its first write."""
    cache = os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                                  os.path.join(_CHECKOUT, ".jax_cache"))
    min_s = os.environ.setdefault(
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "1")
    jax = sys.modules.get("jax")
    if jax is not None:
        # jax read its environment at import; mirror the same values
        jax.config.update("jax_compilation_cache_dir", cache)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          float(min_s))
    return cache
