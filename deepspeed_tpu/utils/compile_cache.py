"""Where this checkout keeps JAX's persistent compilation cache.

The directory is part of the cache key, so it must never move between
processes or runs: replicas of one fleet, the root bench scripts and
``chip_smoke.py`` all share compiled programs only if they all name the
same place.
"""
from __future__ import annotations

import os
from typing import Optional

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
#: ``<checkout>/.jax_cache`` (listed in .gitignore).  The package runs from
#: a source checkout (it is not pip-installed); the directory above it is
#: the checkout's root only then.
DEFAULT_CACHE_DIR = os.path.join(_ROOT, ".jax_cache")


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` wins: JAX reads it by itself, so when
    it is set nothing is configured here.  Otherwise the cache lives at
    the fixed ``DEFAULT_CACHE_DIR`` — unless the package was installed
    into a ``site-packages``, where there is no checkout to keep it in:
    then nothing is set (None) and the variable is the way to place it.
    Call before the first compile; touches no backend."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    if os.path.basename(_ROOT) in ("site-packages", "dist-packages"):
        return None
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
