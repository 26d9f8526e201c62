"""JAX's persistent compilation cache for the entry points.

Entry points (``chip_smoke.py``, ``examples/``, ``repro.launch.train``
and ``repro.launch.serve``) call :func:`enable_compile_cache` first
thing in ``main()``; importing the library never does.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no other
directory is set here.  Otherwise the cache lives at one fixed path in
the checkout: the directory is part of the cache key, so a path built
from a temp name, a pid or the time would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

#: the checkout's own cache directory (listed in .gitignore)
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
