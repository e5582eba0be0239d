"""JAX's persistent compilation cache, kept at one fixed path.

The cache directory is part of what makes an entry hit, so it never comes
from a temp name, a pid or the clock.  ``JAX_COMPILATION_CACHE_DIR``, when
set, is used as JAX reads it; otherwise the cache lives at ``.jax_cache`` in
the checkout (listed in ``.gitignore``).  An installed copy of the package
outside a checkout has no such place, and leaves the cache off.  Entry
points call ``enable_compile_cache()`` before their first compile.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache", "checkout_cache_dir"]


def checkout_cache_dir() -> str | None:
    """``<checkout>/.jax_cache`` when this module runs from a checkout's
    ``src/repro/launch``, else None."""
    root = Path(__file__).resolve().parents[3]
    if not (root / "pyproject.toml").is_file() or not (root / "src" / "repro").is_dir():
        return None
    return str(root / ".jax_cache")


def enable_compile_cache() -> str | None:
    """Turn the persistent cache on; returns the directory in use, or None
    when there is neither ``JAX_COMPILATION_CACHE_DIR`` nor a checkout."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = checkout_cache_dir()
    if path is not None:
        jax.config.update("jax_compilation_cache_dir", path)
    return path
