"""JAX's persistent compilation cache, for the programs that run on the chip.

A served table compiles its whole read-executor grid before the first
request (``TableServer.warm``): dozens of small executables.  With the
persistent cache on, a second process with the same code finds them on
disk instead of compiling them again.

Called by ``chip_smoke.py`` and the benchmark entry points, never on
import and never by the tests.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: Where the cache lives when ``JAX_COMPILATION_CACHE_DIR`` is not set: a
#: fixed directory of the checkout (listed in ``.gitignore``), so the next
#: process finds what this one cached; it never depends on a pid, a time or
#: a temporary name.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as it is (JAX reads it
    itself); otherwise the cache goes to :data:`DEFAULT_DIR`.  Every
    executable is cached, however quickly it compiled: the warm grid is
    many small programs.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


__all__ = ["DEFAULT_DIR", "enable_compile_cache"]
