"""Persistent XLA compilation cache.

A fresh process pays tens of seconds of compiles before the first real
step (each ResNet-20 step program takes the v5e compiler ~30 s); the
programs themselves are stable across runs, so a disk cache turns every
run after the first into a warm start.  The reference amortizes its
(much smaller) graph-bind cost inside one long-lived process — in a
jit-compiled framework the equivalent is making compilation itself
persistent.

The cache is keyed by JAX's hash of the lowered program + compile
options + device kind, so an entry whose program differs is never *hit*,
only ignored; it is safe to share one directory across branches and code
versions.  One case is not covered: JAX strips locations from the
program before it hashes it, and the names ``profile_scope`` gives the
step's ops travel as locations.  Two builds that differ only in scope
names then share an entry, and the later one loads the earlier one's
program, old names and all: same speed, but ``Trainer.step_layers`` and
a profile's op names read the old vocabulary (the chip benchmark's
``unscoped_device_pct`` then reads near 100).  A step that holds a
Pallas kernel is safe, because the kernel's serialized body carries the
names into the hash: every cell of the chip benchmark does, and a change
of this PR 24 run on a cache the parent had filled read 0.36% (PERF.md).
A program with no kernel is not.  The cure is a cleared cache or
``jax_compilation_cache_include_metadata_in_key``, which makes names,
source paths and lines part of every key (a moved checkout or a moved
line then compiles cold, and an ahead-of-time lowering no longer shares
the entry of the call that ran).  The tests turn it on (tests/conftest.py);
the program does not.
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
