"""JAX's persistent compilation cache, placed from outside.

Call `use_compile_cache()` at the start of an entry point, never at
import: tests keep the cache off (a compile for a described chip is
written to it but cannot be read back without one).
"""

from __future__ import annotations

import os
import pathlib

# <checkout>/.jax_cache: a fixed path, because the directory is part of
# the cache key — a path made from a temp name, pid or time never hits
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    Where JAX_COMPILATION_CACHE_DIR is set, JAX already reads it and this
    sets no other; otherwise the cache lives at <checkout>/.jax_cache."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
