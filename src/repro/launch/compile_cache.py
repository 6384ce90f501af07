"""JAX's persistent compilation cache, placed for the entry points.

Entry points call :func:`enable_compile_cache` before their first compile;
importing this module changes nothing.  A run then finds the executables an
earlier run of the same checkout compiled, instead of compiling every round
program again.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache", "DEFAULT_CACHE_DIR"]

# A fixed path inside the checkout: the directory is part of what a later
# run must find again, so it never carries a pid, a time or a temp name.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting (JAX
    reads it at import) and is left as it is; otherwise the cache lives in
    ``<checkout>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
