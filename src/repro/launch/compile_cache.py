"""Where the entry points keep JAX's persistent compilation cache.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; where it is set, nothing here
overrides it. Where it is unset, the cache goes to one fixed directory in the
checkout, ``<repo>/.jax_cache``: a cache only hits when the next process
looks in the same place, so the path never carries a temp name, pid or time.
"""

from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Point the persistent compilation cache at its directory and return
    that directory. Call from an entry point's ``main()`` before the first
    compilation; never at import time or from tests, where it would make
    every later compile in the process write to the checkout."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
