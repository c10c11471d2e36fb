"""Where the launchers keep JAX's persistent compilation cache."""
from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache (gitignored).  A fixed path: the path is part of the
# cache key, so a directory that moved between runs would never hit.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins and nothing else is set
    (JAX reads the variable itself); otherwise the cache lives in
    :data:`CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
