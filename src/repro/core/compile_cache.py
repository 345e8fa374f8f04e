"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``repro.launch.serve``, the examples,
``benchmarks/run.py``) and every process-plane worker call
:func:`use_compile_cache`, so all of them share one cache:

* ``JAX_COMPILATION_CACHE_DIR``, when set — JAX reads it itself, and
  nothing else is set in code;
* otherwise the fixed ``<checkout>/.jax_cache`` (listed in
  ``.gitignore``).  The path is part of the cache key, so it never moves.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """The cache directory this process and its workers share."""
    return os.environ.get(ENV_VAR) or str(CHECKOUT_CACHE)


def use_compile_cache(path: Optional[str] = None) -> str:
    """Turn the persistent cache on at ``path`` (default:
    :func:`compile_cache_dir`) before the first compile; returns it."""
    path = path or compile_cache_dir()
    if os.environ.get(ENV_VAR):
        return path
    import jax

    jax.config.update("jax_compilation_cache_dir", path)
    # cache every program: a restarted worker finds even the small ones
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
