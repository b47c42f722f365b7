"""Where the entry points keep JAX's persistent compilation cache.

Called from the ``main`` of ``launch/train.py`` and ``launch/serve.py`` and
from ``chip_smoke.py`` — never at import, so importing the library leaves
JAX's configuration alone.
"""
from __future__ import annotations

import os

import jax

# <checkout>/.jax_cache: a fixed path, because the directory is part of what
# the cache is keyed on — a path built from a temp name, a pid or the time
# would never hit.
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    sets nothing; otherwise the cache goes to :data:`CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
