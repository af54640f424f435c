"""JAX persistent compilation cache, placed from outside or at a fixed path.

A cold run on the chip spends much of its time compiling (the DLRM-Kaggle
SSU step alone takes about 100 s), and the cache key includes its path, so
the directory must not move between runs.  Entry points call
``enable_compile_cache()`` once, before their first compile; importing this
module changes nothing.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# <checkout>/.jax_cache (listed in .gitignore)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it and no
    other directory is set; otherwise the cache goes to ``DEFAULT_DIR``."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
