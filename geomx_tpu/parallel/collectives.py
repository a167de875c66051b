"""Hierarchical collectives over the HiPS mesh.

These replace the reference's entire push/pull dataflow on the synchronous
path (reference call stack: SURVEY.md §3.3 — worker ZPush → local server
merge → TS_Push → global server merge → pull back down).  A hierarchical
``psum`` over (worker, dc) axes is semantically the two-tier aggregation;
XLA lowers each stage to the matching interconnect's collective (ICI
all-reduce for the worker axis, DCN for the dc axis) and overlaps them with
compute — no engine threads, no explicit messages.
"""

from __future__ import annotations

from typing import Any

import jax
from jax import lax

from geomx_tpu.topology import DC_AXIS, WORKER_AXIS
from geomx_tpu.utils.profiler import profile_scope


def shard_map_compat(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the replication check off: the sync
    algorithms return per-device state under replicated out-specs by
    design (train/state.py)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def tier_scope(axis_name: str):
    """``collective/worker`` / ``collective/dc``: the scope a collective
    over one mesh tier is traced under, so that a profile charges it to
    that tier (telemetry/layers.py).  The sync tiers and compressors open
    it around their own ``lax`` collectives."""
    return profile_scope(f"collective/{axis_name}", category="comm")


# ---- per-leaf collectives (usable inside shard_map) ------------------------

def psum_worker(tree: Any) -> Any:
    """Intra-party aggregation — the worker → local-server merge
    (reference: src/kvstore/kvstore_dist_server.h:1324 `== NumWorkers`)."""
    with tier_scope(WORKER_AXIS):
        return lax.psum(tree, WORKER_AXIS)


def psum_dc(tree: Any) -> Any:
    """Cross-party aggregation — the local-server → global-server merge
    (reference: src/kvstore/kvstore_dist_server.h:1305-1318)."""
    with tier_scope(DC_AXIS):
        return lax.psum(tree, DC_AXIS)


def pmean_worker(tree: Any) -> Any:
    with tier_scope(WORKER_AXIS):
        return lax.pmean(tree, WORKER_AXIS)


def pmean_dc(tree: Any) -> Any:
    with tier_scope(DC_AXIS):
        return lax.pmean(tree, DC_AXIS)


def hier_psum(tree: Any) -> Any:
    """Two-tier sum: ICI stage first, then DCN stage.

    Equivalent to ``psum`` over both axes but staged to mirror HiPS;
    XLA fuses/pipelines the two all-reduces.
    """
    return psum_dc(psum_worker(tree))


def hier_pmean(tree: Any) -> Any:
    return pmean_dc(pmean_worker(tree))


def all_gather_dc(x: jax.Array, axis: int = 0, tiled: bool = False) -> jax.Array:
    """Gather a per-party payload across the global tier. This is the wire
    transfer of a compressed push: each party contributes its (fixed-size)
    compressed gradient; every party reconstructs the aggregate locally —
    the SPMD analogue of server-side decompress-and-merge
    (reference: kvstore_dist_server.h:1099-1114 BSCDecompress into store_)."""
    with tier_scope(DC_AXIS):
        return lax.all_gather(x, DC_AXIS, axis=axis, tiled=tiled)


def party_index() -> jax.Array:
    return lax.axis_index(DC_AXIS)


def worker_index() -> jax.Array:
    return lax.axis_index(WORKER_AXIS)


def axis_size(axis_name: str) -> int:
    return lax.psum(1, axis_name)


def global_worker_rank() -> jax.Array:
    """Linear rank over all workers (reference: kvstore rank per worker)."""
    return party_index() * axis_size(WORKER_AXIS) + worker_index()
