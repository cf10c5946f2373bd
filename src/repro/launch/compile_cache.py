"""JAX's persistent compilation cache, placed where a run can find it again.

A compiled program is keyed by (among other things) the cache directory's
contents, so the directory must not move between runs.  When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this module
sets no directory; otherwise the cache lives at a fixed ``.jax_cache`` in
the checkout (listed in ``.gitignore``).  Either way every program is
cached: JAX's defaults skip programs that compile in under a second, which
are most of this repo's (many small jitted steps), so a warm run would
still recompile them.  Entry points call ``enable_compile_cache()`` once,
before their first compile.
"""
from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
