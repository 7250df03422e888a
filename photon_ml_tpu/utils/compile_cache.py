"""JAX's persistent compilation cache, at a path that can be placed from
outside.

A process that lives for one command recompiles every program unless the
cache survives it, and the cache directory is part of every entry's key:
a directory that moves (a temp name, a user name, a pid) never hits. So
there is one rule, applied by every entry point before its first compile.
"""

from __future__ import annotations

import os

#: The fixed in-checkout directory (git-ignored) used when the environment
#: does not place the cache.
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax has already read it and
    nothing is set in code; otherwise :data:`DEFAULT_DIR` is used."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
