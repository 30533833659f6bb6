"""Where the program keeps what it caches between runs.

Both caches sit at fixed paths inside the checkout unless the caller
names another place, so a second run from the same checkout finds what
the first one wrote.  A cache path is never made from a temp name, a pid
or the time: the path is part of the compile cache's key.

* JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR`` when
  it is set (JAX reads the variable itself and this module sets no
  other), else ``<checkout>/.jax_cache``.
* The kernel autotune registry: ``REPRO_AUTOTUNE_REGISTRY`` when it is
  set, else ``<checkout>/.cache/autotune.json``.

Both in-checkout paths are listed in ``.gitignore``.
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


def autotune_registry_path() -> str:
    """The kernel autotune registry the ops wrappers read."""
    return (os.environ.get("REPRO_AUTOTUNE_REGISTRY")
            or os.path.join(CHECKOUT, ".cache", "autotune.json"))
