"""JAX's persistent compilation cache, switched on by the entry points.

Library modules never touch it: only a ``main`` (or a script's top level)
calls ``enable_compile_cache``, so importing ``repro`` changes no JAX
configuration.
"""

from __future__ import annotations

import os

# fixed location at the repository root (listed in .gitignore)
REPO_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as it is (JAX reads the
    variable itself) and no other directory is configured.  Otherwise the
    cache lives in the fixed ``.jax_cache/`` at the repository root.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
