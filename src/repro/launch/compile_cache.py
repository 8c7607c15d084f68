"""JAX's persistent compilation cache, at one fixed place.

Entry points (``chip_smoke.py``, :func:`repro.launch.train.main`,
``benchmarks/run.py``) call :func:`use_compile_cache` before their first
compile; library code never does, so importing the package changes no
JAX setting.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["REPO_CACHE_DIR", "use_compile_cache"]

#: the cache directory when ``JAX_COMPILATION_CACHE_DIR`` is not set:
#: ``<repo>/.jax_cache`` (git-ignored)
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here. Otherwise the cache goes to
    :data:`REPO_CACHE_DIR`. The path never comes from a temporary name,
    a process id or the time: a later process finds the entries only at
    the same path.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
