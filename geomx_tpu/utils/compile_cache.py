"""Persistent XLA compilation cache.

A fresh process pays tens of seconds of compiles before the first real
step (each ResNet-20 step program takes the v5e compiler ~30 s); the
programs themselves are stable across runs, so a disk cache turns every
run after the first into a warm start.  The reference amortizes its
(much smaller) graph-bind cost inside one long-lived process — in a
jit-compiled framework the equivalent is making compilation itself
persistent.

The cache is keyed by XLA's hash of the lowered program + compile
options + device kind, so stale entries are never *hit*, only ignored;
it is safe to share one directory across branches and code versions.
The directory itself is part of JAX's key, so it must not move between
runs: it is placed from outside through ``JAX_COMPILATION_CACHE_DIR``,
and otherwise sits at one fixed path inside the checkout.
"""

from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.geomx_compile_cache, from this file's own location: the
# same directory whatever the caller's cwd
DEFAULT_CACHE_DIR = str(
    Path(__file__).resolve().parents[2] / ".geomx_compile_cache")


def enable_compile_cache(min_compile_seconds: float = 0.5) -> str | None:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing here sets a directory, in code or in ``os.environ``.  Where
    it is not, the cache is ``<checkout>/.geomx_compile_cache``.
    ``GEOMX_COMPILE_CACHE=0`` disables and returns None.  Entries that
    took less than ``min_compile_seconds`` to compile are not persisted
    (they are cheaper to recompile than to stat).

    Child processes that want the cache call this themselves; they
    resolve the same directory.
    """
    # graftlint: disable=GXL006 — pre-config opt-out
    if os.environ.get("GEOMX_COMPILE_CACHE") == "0":
        return None

    import jax

    # graftlint: disable=GXL006 — JAX's own variable, read to respect it
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      min_compile_seconds)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
