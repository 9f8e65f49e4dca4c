"""Where JAX keeps its persistent compilation cache.

One rule for every entry point (``launch/compress.py``, ``launch/serve.py``,
``launch/train.py``, ``launch/dryrun.py``, ``chip_smoke.py``): when
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing here
names another directory; otherwise the cache is the fixed ``.jax_cache/`` at
the root of the checkout (git ignores it).  A fixed path lets one run's
compiled programs serve the next; a temp, pid or time-stamped directory
would never hit.
"""

from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CACHE_DIR_ENV", "DEFAULT_CACHE_DIR", "enable_compile_cache"]

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on (call before the first compile) and
    return its directory."""
    path = os.environ.get(CACHE_DIR_ENV)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
