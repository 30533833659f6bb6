"""Where the program keeps what it caches between runs.

JAX's persistent compilation cache sits at a fixed path inside the
checkout unless the caller names another place, so a second run from the
same checkout finds what the first one wrote.  The path is never made
from a temp name, a pid or the time: it is part of the cache's key.

``JAX_COMPILATION_CACHE_DIR`` wins when it is set (JAX reads the variable
itself and this module sets no other), else ``<checkout>/.jax_cache``,
which is listed in ``.gitignore``.
"""
from __future__ import annotations

import os

# src/repro/launch/cache.py -> the checkout root
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def compile_cache_dir() -> str:
    """The directory JAX's persistent compilation cache uses."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(CHECKOUT, ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.
    Call before the first compile."""
    import jax
    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    return path

