"""Shared JAX runtime configuration for the binaries, bench, tools and smoke."""

from __future__ import annotations

import os
from typing import Optional

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: Where the persistent compilation cache goes when nobody says otherwise:
#: a FIXED path inside the checkout.  The path is part of how a deployment
#: finds its cache again, so it carries no host-, pid- or time-derived
#: component — a restarted replica, or the next run on a fresh machine
#: that was handed the same directory, hits what the last one compiled.
DEFAULT_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")

#: what the last :func:`enable_compile_cache` returned (None before any)
_cache_dir_in_use: Optional[str] = None


def compile_cache_dir() -> Optional[str]:
    """Where this process persists executables, as :func:`enable_compile_cache`
    decided it; None where it decided on none, or nobody asked it.  The
    program store (vdaf/program_store.py) lives in a subdirectory of it, so
    it is found and cleared with the compile cache."""
    return _cache_dir_in_use


def enable_compile_cache(cache_dir: Optional[str] = None) -> Optional[str]:
    """Turn on XLA's persistent compilation cache; returns the directory
    in use, or None when the cache stays off.

    The limb-arithmetic graphs are large (minutes of compile per VDAF
    shape); with the cache a re-run of the same (circuit, batch) shape —
    a restarted replica, the next bench row, the next smoke — loads its
    executable instead of compiling it.  Every entry point goes through
    this one function.

    Where the directory comes from, in order:

    1. ``JAX_COMPILATION_CACHE_DIR`` — JAX reads it itself, and when it is
       set this function sets NO directory in code: whoever placed the
       cache from outside (an operator's volume, the machine a run was
       handed) wins over everything below.
    2. ``cache_dir`` — the binaries' ``common.compile_cache_dir``.
    3. :data:`DEFAULT_CACHE_DIR`, ``<repo>/.jax_cache``.

    No cache on XLA:CPU: it persists executables as AOT objects whose
    recorded target machine includes compile-time pseudo-features
    (+prefer-no-scatter, +prefer-no-gather) that never appear in the
    loader's host-feature probe, so every cross-process load fails the
    feature check and falls into a pathological slow path (observed: a
    68 s cold-compile test became a 26+ minute hang).  Cold compiles are
    cheaper than poisoned loads.  The guard asks the backend that was
    actually elected, so calling this initializes JAX's backends —
    ``jax.distributed.initialize`` must already have run where it is used.
    """
    import jax

    global _cache_dir_in_use
    _cache_dir_in_use = None
    if jax.default_backend() == "cpu":
        return None
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not env_dir:
        jax.config.update("jax_compilation_cache_dir", cache_dir or DEFAULT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # Persist only JAX's executable cache: XLA's own per-kernel AOT caches
    # embed host machine code and have hung when loaded on another host.
    jax.config.update("jax_persistent_cache_enable_xla_caches", "none")
    _cache_dir_in_use = env_dir or jax.config.jax_compilation_cache_dir
    return _cache_dir_in_use
