"""Where JAX's persistent compile cache lives, for every entry point.

The CLI's main, chip_smoke.py and bench.py call `place()` before they
compile anything.  Where JAX_COMPILATION_CACHE_DIR is set, JAX reads that
directory itself and nothing is set in code; otherwise the cache goes to a
fixed `<checkout>/.jax_cache` (gitignored).  Tests keep the cache off
(tests/conftest.py sets JAX_ENABLE_COMPILATION_CACHE=false).

Either way the cache key includes the programs' metadata: a compiled
program keeps the `op_name` of every op, which carries the stages'
`jax.named_scope` names (obs/tracer.py `DEVICE_SCOPES`) into a profiler
trace.  JAX's default key leaves the metadata out, so a program whose
scopes changed would load the executable of its older self, and its trace
would show the old names.  The metadata holds the source lines of the
call stack that traced each program, so an edit to one of those lines, or
another checkout path, compiles the program anew.
"""

from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def place() -> str:
    """Point the compile cache at its directory; return the directory."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
