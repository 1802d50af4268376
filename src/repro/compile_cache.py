"""JAX's persistent compilation cache at one fixed directory.

A process that compiles a program it compiled before — a later chip run,
a second benchmark, an example — reads the executable back instead of
recompiling it.  The directory is part of the cache's identity, so it is
never a temporary, per-process or per-run path.

Entry points call :func:`enable_compile_cache` first thing; nothing calls
it at import, so importing :mod:`repro` never changes JAX's configuration.
"""

from __future__ import annotations

import os
import pathlib

import jax

#: the cache directory when ``JAX_COMPILATION_CACHE_DIR`` is unset:
#: ``.jax_cache/`` at the checkout root (listed in ``.gitignore``)
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory (JAX reads
    it itself; no other is set in code); otherwise :data:`DEFAULT_DIR`.
    Every program is cached however fast it compiled: a proxy's small
    programs compile in well under JAX's default one-second floor, and a
    cold process would otherwise recompile every one of them."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or ""
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
