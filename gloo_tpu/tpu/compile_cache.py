"""JAX's persistent compilation cache, placed from outside the program.

A compiled program is found again only where it was written, so the
directory is either the one the environment names or a fixed path in the
checkout: never a temporary name, a pid or the time.
"""

from __future__ import annotations

import os

import jax

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    `JAX_COMPILATION_CACHE_DIR`, when set, is used as it is and no other
    directory is set. Otherwise the cache lives at `<repo>/.jax_cache`.
    Call before the first compile.
    """
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(_REPO_ROOT, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    return path
