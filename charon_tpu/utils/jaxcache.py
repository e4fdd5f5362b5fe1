"""Persistent JAX compilation cache, shared by every entry point.

The fused sigagg and verify graphs take minutes to compile cold and are
identical run to run. `enable()` is called once by each entry point
(app.assemble, bench.py, chip_smoke.py) before its first compile; nothing
enables the cache as a side effect of an import.

Placement: JAX itself reads JAX_COMPILATION_CACHE_DIR into its
`jax_compilation_cache_dir` option, and enable() then uses exactly that
directory. Without the variable the cache goes to `<checkout>/.jax_cache`,
resolved from this package's location, so every process of one checkout
shares it whatever its working directory.
"""

from __future__ import annotations

import os
import pathlib


def cache_dir() -> str:
    """The directory enable() points the cache at: JAX_COMPILATION_CACHE_DIR
    when set, else <checkout>/.jax_cache."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        pathlib.Path(__file__).resolve().parents[2] / ".jax_cache")


def enable() -> str:
    """Point jax's persistent compilation cache at cache_dir(), caching
    every executable that took at least a second to compile. Idempotent;
    call it before the first compile. Returns the directory."""
    import jax

    path = cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return path
