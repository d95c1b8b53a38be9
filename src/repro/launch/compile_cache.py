"""Where JAX keeps its persistent compilation cache for this checkout.

A compiled program is found again only under the same cache path, so the
path is fixed: ``JAX_COMPILATION_CACHE_DIR`` where the environment sets it
(JAX reads that variable itself, and nothing is set here), otherwise
``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    Call before the first compile of the process."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
