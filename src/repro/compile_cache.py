"""JAX's persistent compilation cache at a fixed place in the checkout.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
:func:`enable_compile_cache` sets nothing.  Otherwise the cache goes to
``<root>/.jax_cache`` (listed in ``.gitignore``).  The path is fixed
because a cache entry is found again only under the directory it was
written to.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax


def enable_compile_cache(root: os.PathLike) -> None:
    """Point JAX's compilation cache at ``<root>/.jax_cache`` unless the
    environment already names a cache directory.  Call before the first
    compilation."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir",
                      str(Path(root).resolve() / ".jax_cache"))
