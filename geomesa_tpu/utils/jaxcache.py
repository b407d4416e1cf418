"""Persistent XLA compilation cache.

The reference ships its server-side code as a pre-built jar to the
tablet servers (geomesa-accumulo-distributed-runtime), so scan
machinery never compiles at query time. The TPU analog: persist XLA
executables across processes so only the FIRST process pays the
trace+compile of the scan/join kernels.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and this module
sets no directory. Otherwise the cache lives at ``<checkout>/.jax_cache``:
a fixed path, because the path is part of the cache's key and a directory
that moves never hits. ``JAX_ENABLE_COMPILATION_CACHE=false`` turns the
cache off.
"""

from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def ensure_compile_cache() -> None:
    """Point JAX at the persistent compilation cache and count its backend
    compiles (called when each kernel module is imported; repeated calls
    set the same values)."""
    from ..obs.runtime import runtime
    runtime.watch_compiles()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # cache everything that took meaningful compile time; the default
    # threshold skips exactly the 1-2s kernels that add up
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
