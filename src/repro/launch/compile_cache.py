"""Where JAX keeps its persistent compilation cache.

JAX reads `JAX_COMPILATION_CACHE_DIR` itself; when it is set, nothing
here overrides it.  Otherwise the entry points (`launch/serve.py`,
`chip_smoke.py`) keep the cache in one fixed directory of the checkout,
`.jax_cache/` (git-ignored).  The path is part of the cache key, so it
never contains a temporary name, a process id or a time.
"""

from __future__ import annotations

import os

from repro import CHECKOUT

ENV = "JAX_COMPILATION_CACHE_DIR"


def use_compile_cache() -> str:
    """Point JAX at its compilation cache; returns the directory used."""
    import jax

    path = os.environ.get(ENV)
    if not path:
        path = os.path.join(CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
