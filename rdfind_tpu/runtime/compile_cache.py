"""Where JAX's persistent compile cache lives, for every entry point.

The CLI's main, chip_smoke.py and bench.py call `place()` before they
compile anything.  Where JAX_COMPILATION_CACHE_DIR is set, JAX reads that
directory itself and nothing is set in code; otherwise the cache goes to a
fixed `<checkout>/.jax_cache` (gitignored).  The path is part of the
cache's key, so a directory that moves never hits.  Tests keep the cache
off (tests/conftest.py sets JAX_ENABLE_COMPILATION_CACHE=false).
"""

from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def place() -> str:
    """Point the compile cache at its directory; return the directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
