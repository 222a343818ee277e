"""JAX's persistent compilation cache, as the entry points configure it."""
from __future__ import annotations

import os
from pathlib import Path

#: fixed per checkout: the cache key includes the path, so it must not move
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> None:
    """Keep compiled programs across runs of an entry point.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses it and
    nothing changes; otherwise the cache lives in ``.jax_cache`` at the root
    of the checkout.  Call it from ``__main__`` code only, before the first
    compile — never from library code that tests import."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
