"""JAX's persistent XLA compilation cache, placed once for every entry
point.

Not the repo's own AOT-executable store (utils/warmcache.py, keyed on
mesh device ids for elastic recovery): this is the cache XLA itself
keeps, so a second process compiling the same step loads it instead of
recompiling. The directory is part of the cache key, so it must be the
same on every run — never a temp, pid or time name.
"""

from __future__ import annotations

import os

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def use_compile_cache() -> str:
    """Call before the first compile. Where `JAX_COMPILATION_CACHE_DIR`
    is set JAX reads it itself and nothing is configured here; otherwise
    the cache goes to `<checkout>/.jax_cache`. Returns the directory in
    use."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    path = os.path.join(_REPO, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
