"""Where JAX's persistent compilation cache lives, for every entry point.

A cold process compiles every program again; on a chip a full training
step takes tens of seconds to compile.  The persistent cache keeps the
executables between processes.  Its directory is part of what makes an
entry findable again, so it is fixed: ``$JAX_COMPILATION_CACHE_DIR`` when
the environment sets it (JAX reads that variable itself, and nothing here
overrides it), otherwise ``<repo>/.jax_cache``.

The command-line entry points call `enable_compile_cache` before their
first compile; the tests never do, so they leave the cache off.
"""

from __future__ import annotations

import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
