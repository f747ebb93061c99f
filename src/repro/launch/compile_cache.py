"""Where the entry points keep JAX's persistent compile cache.

A compile cache is keyed by its path, so it has to stay put between runs:
`$JAX_COMPILATION_CACHE_DIR` when it is set (JAX reads that variable
itself, so nothing is set here), otherwise `<repo>/.jax_cache`, fixed
inside the checkout.  Entry points call `use_compile_cache()` from
`main()`, never at import.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO = pathlib.Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
