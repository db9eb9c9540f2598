"""JAX's persistent compilation cache, placed once for every entry point.

``JAX_COMPILATION_CACHE_DIR``, when set, names the directory and jax reads
it itself; otherwise the cache lives at one fixed path inside the checkout
(``.jax_cache/``, git-ignored).  The path is part of what a later run must
find again, so it never depends on the working directory.
"""

from __future__ import annotations

import os
import pathlib

import jax

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on for every compile; returns its path."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # Pallas kernels compile in well under the default 1 s threshold; a
    # warm start should find them too.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
