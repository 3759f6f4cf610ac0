"""Where JAX keeps its persistent compilation cache for this checkout.

A set `JAX_COMPILATION_CACHE_DIR` wins: JAX reads it on its own and the
code sets no directory at all. Otherwise the cache lives at the fixed
`<checkout>/.jax_cache` — the path is part of the cache key, so it must
not depend on a temp dir, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> Optional[str]:
    """Point JAX's persistent cache at `<checkout>/.jax_cache` unless
    `JAX_COMPILATION_CACHE_DIR` is set; returns the directory it set,
    or None when the environment's choice stands."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    import jax
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
