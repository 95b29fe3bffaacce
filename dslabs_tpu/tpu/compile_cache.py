"""Persistent XLA compile-cache wiring (one place, one path).

The cache directory is part of the cache's key, so a directory that
moves never hits.  This module is the single seam:

* where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it
  and this module sets no directory at all;
* where it is not set, the cache lives at ``<checkout>/.jax_cache``,
  computed from this package's own location — the same path from every
  process and every working directory (``.gitignore`` lists it).

Together with the engines' AOT warm-up (``ShardedTensorSearch
.aot_warmup``) the second construction of any config pays near-zero
compile: the warm-up's ``.lower().compile()`` hits the on-disk cache
instead of XLA.
"""

from __future__ import annotations

import os

__all__ = ["setup", "cache_dir", "DEFAULT_DIR"]

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def cache_dir() -> str:
    """The persistent-compile-cache directory currently in effect."""
    import jax

    return jax.config.jax_compilation_cache_dir


def setup() -> str:
    """Enable JAX's persistent compilation cache and return its
    directory: the one ``JAX_COMPILATION_CACHE_DIR`` names (left to
    JAX), else :data:`DEFAULT_DIR`.  Idempotent — every engine
    constructor calls it."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR") \
            and jax.config.jax_compilation_cache_dir != DEFAULT_DIR:
        from jax.experimental.compilation_cache import \
            compilation_cache as cc

        os.makedirs(DEFAULT_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
        # The runtime holds a cache singleton initialised with the dir
        # at FIRST use — without a reset, a dir change after any cached
        # compile is silently ignored.
        cc.reset_cache()
    # Cache quick compiles too: the search programs are rebuilt by
    # every process that constructs an engine, and the key is
    # platform-specific anyway.
    if jax.config.jax_persistent_cache_min_compile_time_secs > 0.5:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          0.5)
    return jax.config.jax_compilation_cache_dir
