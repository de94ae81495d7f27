"""Placement of JAX's persistent compilation cache.

Call ``use_compile_cache()`` once, before the first compile.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no other
directory is set here.  Otherwise the cache lives at the fixed path
``<repo>/.jax_cache`` (listed in ``.gitignore``), so that a later process
on the same checkout finds what an earlier one compiled.  The path is never
built from a temporary name, a pid or the time: a cache that moves never
hits.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def use_compile_cache() -> str:
    """Turn the persistent cache on and return the directory it uses."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    # the decision path's kernels compile in well under JAX's 1 s default
    # threshold: cache every program, not only the slow ones
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
