"""Persistent compile cache placement for the program's entry points.

``use_compile_cache()`` is called at start-up by ``chip_smoke.py``,
``benchmarks/run.py`` and the examples — never at import of a ``repro``
module, so test workers never write a cache.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
changed.  Otherwise the cache goes to ``.jax_cache/`` at the root of the
checkout: a fixed path, because the path is part of what a later run must
find again.  Child processes inherit the choice through the environment.
"""

from __future__ import annotations

import os
import sys

__all__ = ["CACHE_DIR", "use_compile_cache"]

CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), os.pardir, os.pardir, os.pardir,
    ".jax_cache"))


def use_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory; returns it."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    if "jax" in sys.modules:        # already imported: env is read at import
        sys.modules["jax"].config.update("jax_compilation_cache_dir",
                                         CACHE_DIR)
    return CACHE_DIR
