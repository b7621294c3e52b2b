"""JAX persistent compilation cache, placed from outside or at a fixed path.

Entry points (``chip_smoke.py``, ``launch/train.py``, ``launch/serve.py``,
``benchmarks/run.py``) call ``use_compile_cache()`` once in ``main``;
importing the package never touches JAX configuration.
"""
from __future__ import annotations

import os
from pathlib import Path

# <repo>/.jax_cache (listed in .gitignore). A fixed path: the cache key
# includes it, so a directory that moved between runs would never hit.
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and it is
    left alone; otherwise the cache goes to ``DEFAULT_DIR``."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    DEFAULT_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
