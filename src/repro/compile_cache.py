"""JAX persistent compilation cache for the entry points.

Every launcher ``main()`` (``launch/serve.py``, ``launch/tune.py``,
``launch/train.py``, ``benchmarks/run.py``) and ``chip_smoke.py`` calls
``enable_compile_cache()`` once, before its first compile. Library modules
never do: importing the package leaves JAX's cache configuration alone.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
is set here. Otherwise the cache lives at one fixed directory of the
checkout, ``.jax_cache/`` (git-ignored): the directory is part of the cache
key, so a path built from a temp name, a pid or the time would never hit.
"""
from __future__ import annotations

import os

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
