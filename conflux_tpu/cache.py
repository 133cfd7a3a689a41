"""Persistent XLA compilation cache, shared by every entry point.

The at-scale programs cost minutes of compile each (the N=32768 LU is
about a minute and a half per config on a v5e), so the serve path
(`conflux_tpu.serve`), the miniapp CLIs and `chip_smoke.py` all call
:func:`enable_persistent_cache` at startup: a second process
hitting the same (geometry, knobs) config deserializes the executable.

Where the cache lives:

1. ``$JAX_COMPILATION_CACHE_DIR`` when it is set. JAX reads that variable
   itself, so this module then sets no directory in code.
2. Otherwise the fixed ``<repo>/.jax_cache`` (listed in .gitignore). The
   path is part of the cache's key, so it never depends on the process,
   the time or the user's home directory.

A failure to configure the cache raises: a cache that silently stays
off costs minutes per process and hides why.
"""

from __future__ import annotations

import os

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")

_ENABLED_AT: str | None = None


def default_cache_dir() -> str:
    """The directory :func:`enable_persistent_cache` would use (no
    filesystem side effects)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or REPO_CACHE_DIR


def enable_persistent_cache(*, min_compile_secs: float = 10.0) -> str:
    """Switch on jax's persistent compilation cache.

    `min_compile_secs` filters trivial programs out of the cache (the
    default 10 s keeps every at-scale factorization but skips the
    sub-second host utilities); the min-entry-size filter is zeroed so the
    time threshold is the only admission rule — small serialized
    executables of multi-minute compiles were otherwise dropped.

    Returns the cache directory. Safe to call many times (first call
    wins).
    """
    global _ENABLED_AT
    if _ENABLED_AT is not None:
        return _ENABLED_AT
    import jax

    cache = default_cache_dir()
    os.makedirs(cache, exist_ok=True)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      min_compile_secs)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    _ENABLED_AT = cache
    return cache


def cache_enabled() -> bool:
    return _ENABLED_AT is not None


def cache_dir() -> str | None:
    """The directory of the live persistent cache, or None when disabled.

    The serve engine reports this in its startup/bench metadata so an
    operator can tell whether prewarmed compiles will survive the process
    (a cold replica deserializes instead of re-paying the compile)."""
    return _ENABLED_AT
