"""Persistent XLA compilation cache — the one place that configures it.

Compiled executables are cached on disk keyed by HLO hash, so re-runs of
the same program (re-launches, supervisor restarts, a serve process
after its trainer) skip compilation. The directory is part of nothing the
key hashes, but a cache that moves between runs never hits — so there is
exactly one rule for where it lives:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing here
  names a directory;
- otherwise ``<checkout>/.jax_cache``, found from this package's own
  location (listed in ``.gitignore``).

Every entry point (``dcp-train``, ``dcp-serve``, ``dcp-generate``,
``perfbench/run.py``, ``tests/conftest.py``) calls
:func:`enable` with no argument; nothing else touches
``jax_compilation_cache_dir``.
"""

from __future__ import annotations

import os

_CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable() -> str:
    """Turn on the persistent compile cache (idempotent, safe before or
    after backend init) and return the directory in use."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = _CHECKOUT_CACHE
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # cache everything: the default thresholds skip small/fast programs,
    # and a serving run compiles many of those
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cache_dir
