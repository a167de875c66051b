"""Compressed-domain aggregation: the homomorphic sparse/quantized merge.

Every aggregation point used to leave the compressed domain before
summing: the dc tier all-gathered each party's (value, index) pairs and
scatter-added them into a dense bucket, and the quantized streams
(fp16 / 2-bit) were unpacked per party before the fp32 accumulate.
This module keeps the merge IN the compressed domain (ROADMAP item 1):

**Owner-routed sparse allreduce** (:func:`sparse_allreduce`) — the
Ok-Topk shape ("Near-Optimal Sparse Allreduce", PAPERS.md):

1. *route*: the index space ``[0, n)`` splits into ``P`` contiguous
   owner ranges; each party's ``k`` pairs sort by owner (integer
   arithmetic, exact) into fixed-``slots`` per-destination buffers,
   and one ``all_to_all`` delivers every pair to its range owner.
   ``slots = min(k, ceil(slack*k/P) + 8)`` (``GEOMX_SPARSE_AGG_SLACK``,
   default 2.0): balanced top-k indices land ~``k/P`` per owner, and
   pairs past a destination's budget are NOT silently lost — they
   return to the caller for error-feedback reinjection;
2. *merge*: the owner merges its received pairs by sorted-index
   segment sum (ops/merge_pallas.py — the Pallas kernel with a
   bit-identical jnp reference), never materializing anything larger
   than the pair stream;
3. *re-select*: the owner keeps the top ``ceil(pull_slack*k/P) + 8``
   merged pairs by magnitude (``GEOMX_SPARSE_AGG_PULL_SLACK``, default
   2.0) — its share of the global result's sparse budget, the
   reference's pull-side multiplier semantics;
4. *return*: one ``all_gather`` of the per-owner selections, and ONE
   final decompress lands the global aggregate — total per-chip wire
   is ``O(k)`` regardless of party count, vs the gather path's
   ``O(k*P)``, and the final scatter touches ``O(k)`` pairs, not
   ``k*P``.

**Quantized-lattice allreduce** (:func:`lattice_allreduce`) — the THC
move ("Tensor Homomorphic Compression", PAPERS.md): negotiate ONE scale
across the axis (a scalar ``pmax``), quantize every party onto the
shared integer lattice with ``P``-fold headroom, and let the collective
sum the codes exactly (integer psum is associative — no per-party
dense fp32 intermediates, one dequantize at the end).  fp16 streams
ride an int16 lattice (same 2-byte wire, and ``P/32767`` relative
quantization error — finer than fp16's 2^-10 mantissa for small
meshes); 2-bit streams psum their ±1 sign codes as int8 (the static
threshold IS the negotiated scale).

**Host-plane pair merge** (:func:`merge_pairs_host`) — numpy, no jax:
the global tier's sorted-index merge (service/server.py).  Contributions
concatenate in the caller's canonical (sorted-sender) order, stable-sort
by index, and ``np.add.reduceat`` folds each segment left-to-right — a
deterministic O(k log k) merge whose bits cannot depend on push arrival
order.

Everything here is gated by ``GEOMX_SPARSE_AGG`` (default off: the
legacy gather-then-scatter path stays byte-identical) or the explicit
``sparse_agg=`` compressor knobs.
"""

from __future__ import annotations

import math
from typing import Tuple

import numpy as np


def sparse_agg_enabled() -> bool:
    """``GEOMX_SPARSE_AGG=1`` turns the compressed-domain aggregation
    path on for every compressor that implements it (off by default:
    the merged result carries re-selection truncation semantics the
    legacy path does not, so it is an explicit opt-in)."""
    import os

    # graftlint: disable=GXL006 — build-time gate
    return os.environ.get("GEOMX_SPARSE_AGG", "0").strip().lower() in (
        "1", "true", "yes", "on")


def _env_slack(var: str, default: float) -> float:
    import os

    # graftlint: disable=GXL006 — build-time knob
    raw = os.environ.get(var)
    try:
        return float(raw) if raw else default
    except ValueError:
        return default


def push_slots(k: int, num_parties: int, slack: "float | None" = None) -> int:
    """Per-destination slot budget for the owner-routing all_to_all."""
    if slack is None:
        slack = _env_slack("GEOMX_SPARSE_AGG_SLACK", 2.0)
    return max(1, min(int(k), int(math.ceil(slack * k / num_parties)) + 8))


def pull_budget(k: int, num_parties: int,
                slack: "float | None" = None) -> int:
    """Per-owner re-selection budget for the return leg: this shard's
    share of the global result's ~``slack*k`` sparse budget."""
    if slack is None:
        slack = _env_slack("GEOMX_SPARSE_AGG_PULL_SLACK", 2.0)
    return max(1, int(math.ceil(slack * k / num_parties)) + 8)


def owner_shard_size(n: int, num_parties: int) -> int:
    """Contiguous owner-range width: party ``p`` owns indices
    ``[p*S, min((p+1)*S, n))``."""
    return -(-int(n) // int(num_parties))


def owner_route(vals, idx, n: int, num_parties: int, slots: int):
    """Sort a party's pairs into fixed-slot per-owner buffers.

    Returns ``(buf_vals [P, slots], buf_idx [P, slots], of_vals [k],
    of_idx [k])`` — ``of_*`` are the overflow pairs that did not fit
    their destination's slot budget, with non-overflow positions mapped
    to the out-of-range index ``n`` so the caller can reinject them
    into its error-feedback buffer with one ``mode="drop"`` scatter.
    All routing arithmetic is integer (sort, cummax) — exact and
    deterministic."""
    import jax
    import jax.numpy as jnp

    k = vals.shape[0]
    S = owner_shard_size(n, num_parties)
    owner = jnp.where(idx >= 0, idx // S, num_parties).astype(jnp.int32)
    order = jnp.argsort(owner, stable=True)
    sowner = owner[order]
    svals = vals[order]
    sidx = idx[order]
    pos = jnp.arange(k, dtype=jnp.int32)
    prev = jnp.concatenate([jnp.full((1,), -1, jnp.int32), sowner[:-1]])
    head = sowner != prev
    seg_start = jax.lax.cummax(jnp.where(head, pos, 0))
    segrank = pos - seg_start
    real = sowner < num_parties
    fits = real & (segrank < slots)
    dest = jnp.where(fits, sowner * slots + segrank, num_parties * slots)
    buf_v = jnp.zeros((num_parties * slots + 1,), jnp.float32) \
        .at[dest].set(jnp.where(fits, svals, 0.0))[:-1]
    buf_i = jnp.full((num_parties * slots + 1,), -1, jnp.int32) \
        .at[dest].set(jnp.where(fits, sidx, -1))[:-1]
    overflow = real & (segrank >= slots)
    of_vals = jnp.where(overflow, svals, 0.0)
    of_idx = jnp.where(overflow, sidx, n).astype(jnp.int32)
    return (buf_v.reshape(num_parties, slots),
            buf_i.reshape(num_parties, slots), of_vals, of_idx)


def sparse_allreduce(vals, idx, n: int, axis_name: str, axis_size: int,
                     decompress, *, ef_buffer=None,
                     slack: "float | None" = None,
                     pull_slack: "float | None" = None):
    """The owner-routed compressed-domain allreduce (module docstring).

    ``decompress(vals, idx, n)`` lands the FINAL merged selection
    densely — the one dense materialization on the whole path (the
    caller's existing fused/jnp scatter-add; GX-PURITY-001's
    post-collective rule counts it as the single allowed densify).
    ``ef_buffer`` (the caller's dense error-feedback velocity) absorbs
    the routing overflow — pairs past a destination's slot budget —
    BEFORE the collectives launch, so their mass retries next round;
    returns ``(dense_out, new_ef_buffer)`` (``new_ef_buffer`` is None
    when no buffer was handed in).  The merge is ops/merge_pallas.py's:
    a Pallas kernel on a TPU, its bit-identical jnp tree elsewhere."""
    import jax.numpy as jnp
    from jax import lax

    from geomx_tpu.ops.dispatch import merge_pairs
    from geomx_tpu.telemetry.probes import record_inline

    k = int(vals.shape[0])
    P = int(axis_size)
    slots = push_slots(k, P, slack)
    kr = min(P * slots, pull_budget(k, P, pull_slack))
    buf_v, buf_i, of_vals, of_idx = owner_route(vals, idx, n, P, slots)
    if ef_buffer is not None:
        # overflow reinjection binds HERE (pre-collective): the mass
        # stays in the velocity, and the post-collective purity walk
        # sees exactly one densify — the final decompress
        ef_buffer = ef_buffer.at[of_idx].add(of_vals, mode="drop")
    rv = lax.all_to_all(buf_v, axis_name, split_axis=0, concat_axis=0)
    ri = lax.all_to_all(buf_i, axis_name, split_axis=0, concat_axis=0)
    # rows arrive in party order regardless of wall-clock scheduling:
    # the merged bits are a function of the contribution multiset alone
    mvals, midx = merge_pairs(rv.reshape(-1), ri.reshape(-1), P)
    score = jnp.where(midx >= 0, jnp.abs(mvals), -1.0)
    top_score, top_pos = lax.top_k(score, kr)
    tvals = jnp.where(top_score >= 0, mvals[top_pos], 0.0)
    tidx = jnp.where(top_score >= 0, midx[top_pos], -1).astype(jnp.int32)
    # merged mass past the pull budget is DROPPED (the reference's
    # pull-side multiplier truncation); surface the fraction so tuning
    # can see it (telemetry/probes.py inline sink — op-free when off)
    record_inline(
        "sparse_agg_pull_dropped_fraction",
        lambda: 1.0 - jnp.sum(tidx >= 0)
        / jnp.maximum(jnp.sum(midx >= 0), 1))
    av = lax.all_gather(tvals, axis_name).reshape(-1)
    ai = lax.all_gather(tidx, axis_name).reshape(-1)
    return decompress(av, ai, n), ef_buffer


def sparse_wire_bytes(k: int, num_parties: int) -> int:
    """Payload-convention bytes one party contributes per allreduce on
    the owner-routed path: the all_to_all buffers (``P*slots`` value +
    index pairs) plus the return-leg selection (``kr`` pairs), 8 bytes
    per (fp32, int32) pair — what the traced collectives actually
    carry (analysis/passes.py ``audit_wire_accounting``)."""
    P = max(1, int(num_parties))
    slots = push_slots(k, P)
    kr = min(P * slots, pull_budget(k, P))
    return 8 * (P * slots + kr)


# ---------------------------------------------------------------------------
# quantized-lattice allreduce (THC)
# ---------------------------------------------------------------------------

# int16 lattice headroom: codes scale to +-(32767 // P) so the exact
# integer psum of P parties cannot overflow the wire dtype
_INT16_MAX = 32767
_INT8_MAX = 127


def lattice_allreduce_fp16(g, axis_name: str, axis_size: int):
    """Sum ``g`` across the axis on a shared int16 lattice: one scalar
    ``pmax`` negotiates the scale, every party quantizes onto the same
    grid with ``P``-fold headroom, the collective sums CODES (exact —
    integer addition is associative), and one dequantize lands fp32.
    Same 2-byte wire as the fp16 cast it replaces; no per-party dense
    intermediate ever exists."""
    import jax.numpy as jnp
    from jax import lax

    if axis_size > _INT16_MAX:
        raise ValueError(
            f"int16 lattice headroom supports at most {_INT16_MAX} "
            f"parties, got {axis_size}")
    q = _INT16_MAX // int(axis_size)
    gf = g.astype(jnp.float32)
    scale = lax.pmax(jnp.max(jnp.abs(gf)), axis_name)
    safe = jnp.where(scale > 0, scale, 1.0)
    codes = jnp.round(gf / safe * q).astype(jnp.int16)
    total = lax.psum(codes, axis_name)
    return total.astype(jnp.float32) * (safe / q) \
        * jnp.where(scale > 0, 1.0, 0.0)


def lattice_allreduce_signs(signs, threshold: float, axis_name: str,
                            axis_size: int):
    """2-bit lattice sum: per-party sign codes (int8 in {-1, 0, +1})
    psum exactly on the wire — the static ±``threshold`` grid is the
    already-negotiated shared scale — and scale once at the end."""
    import jax.numpy as jnp
    from jax import lax

    if axis_size > _INT8_MAX:
        raise ValueError(
            f"int8 sign-lattice headroom supports at most {_INT8_MAX} "
            f"parties, got {axis_size}")
    total = lax.psum(signs.astype(jnp.int8), axis_name)
    return total.astype(jnp.float32) * threshold


# ---------------------------------------------------------------------------
# host-plane sorted-index merge (the global tier's kernel)
# ---------------------------------------------------------------------------

def _native_merge(vals: np.ndarray, idx: np.ndarray):
    """Route the concatenated pair set through the fast-path merge when
    the native wire path is enabled: the nogil C++ ``gx_merge_pairs``
    if ``libgeops.so`` is built, else a numpy replica of its SEQUENTIAL
    left-to-right float32 fold (vectorized across segments by
    accumulation round, so it costs O(max duplicates) passes — the
    duplicate count is the party count, small).  The replica is pinned
    bit-identical to the C++ by tests/test_wire_fastpath.py, so which
    one ran is unobservable in the merged bits.  Returns ``None`` under
    ``GEOMX_NATIVE_WIRE=0`` — that switch forces the UNTOUCHED legacy
    ``np.add.reduceat`` fold (pairwise summation, different low bits
    than the sequential tree) exactly as shipped before the fast path
    existed."""
    from geomx_tpu.service.protocol import binary_wire_enabled
    if not binary_wire_enabled():
        return None
    from geomx_tpu.runtime import native
    out = native.merge_pairs(vals, idx)
    if out is not None:
        return out
    keep = idx >= 0
    vals, idx = vals[keep], idx[keep]
    if idx.size == 0:
        return (np.zeros((0,), np.float32), np.zeros((0,), np.int64))
    order = np.argsort(idx, kind="stable")
    si, sv = idx[order], vals[order]
    head = np.ones(si.size, bool)
    head[1:] = si[1:] != si[:-1]
    starts = np.flatnonzero(head)
    lens = np.diff(np.append(starts, si.size))
    merged = sv[starts].copy()
    for r in range(1, int(lens.max())):
        m = lens > r
        merged[m] = merged[m] + sv[starts[m] + r]
    return merged, si[starts]


def merge_pairs_host(parts) -> Tuple[np.ndarray, np.ndarray]:
    """Merge (value, index) contributions by index on the host — the
    GeoPSServer round-gate kernel (service/server.py).

    ``parts`` is an iterable of ``(vals, idx)`` numpy pairs in the
    caller's CANONICAL order (sorted sender id): concatenation order +
    stable index sort + a fixed per-segment fold define the summation
    tree completely, so the merged bits are a function of the
    contribution set alone — never of push arrival order.  Which fold:
    the fast path (native wire enabled, default) folds each segment
    SEQUENTIALLY left-to-right in float32 (C++ ``gx_merge_pairs`` or
    its pinned-identical numpy replica); ``GEOMX_NATIVE_WIRE=0`` keeps
    the original ``np.add.reduceat`` pairwise fold byte-for-byte.
    Either way the tree is deterministic per switch setting.  Sentinel
    pairs (index < 0) drop.  Cost: O(K log K) in the total pair count
    K, independent of the dense length.  Returns compact ``(vals fp32,
    idx int64)`` sorted by index, indices unique."""
    vs, is_ = [], []
    for v, i in parts:
        vs.append(np.asarray(v, np.float32).reshape(-1))
        is_.append(np.asarray(i).reshape(-1).astype(np.int64))
    if not vs:
        return (np.zeros((0,), np.float32), np.zeros((0,), np.int64))
    vals = np.concatenate(vs)
    idx = np.concatenate(is_)
    merged = _native_merge(vals, idx)
    if merged is not None:
        return merged
    keep = idx >= 0
    vals, idx = vals[keep], idx[keep]
    if idx.size == 0:
        return (np.zeros((0,), np.float32), np.zeros((0,), np.int64))
    order = np.argsort(idx, kind="stable")
    si, sv = idx[order], vals[order]
    head = np.ones(si.size, bool)
    head[1:] = si[1:] != si[:-1]
    starts = np.flatnonzero(head)
    return np.add.reduceat(sv, starts).astype(np.float32), si[starts]


# the concatenated-pair wire format (values then f32-cast indices) is
# index-exact only below this bound — producers must fall back to a
# dense payload past it, consumers refuse the sparse store/reply
PAIR_WIRE_MAX_N = 1 << 24


def encode_pairs_payload(vals: np.ndarray, idx: np.ndarray) -> np.ndarray:
    """(vals, idx) -> the concatenated pair wire payload (values first,
    then indices cast to f32 — exact below :data:`PAIR_WIRE_MAX_N`)."""
    return np.concatenate([np.asarray(vals, np.float32).reshape(-1),
                           np.asarray(idx, np.float32).reshape(-1)])


def decode_pairs_payload(payload: np.ndarray):
    """Inverse of :func:`encode_pairs_payload`: ``(vals fp32, idx
    int64)`` — sentinels (< 0) preserved for the caller's mask."""
    pairs = np.asarray(payload, np.float32).reshape(-1)
    k = pairs.size // 2
    return pairs[:k], pairs[k:].astype(np.int64)


def densify_pairs_host(vals: np.ndarray, idx: np.ndarray, n: int,
                       out: "np.ndarray | None" = None) -> np.ndarray:
    """Scatter a (value, index) pair set into a dense fp32 vector — the
    ONE densify a sparse-merged round ever pays, and only when a dense
    consumer actually asks (lazy value materialization in
    service/server.py; the client-side decompress of a sparse pull).
    Sentinel pairs (index < 0) drop; duplicate indices SUM (merged sets
    are unique by construction, but a raw push payload is not — add
    semantics keep every densify path consistent with
    :func:`merge_pairs_host` and the legacy per-push densify)."""
    if out is None:
        out = np.zeros((int(n),), np.float32)
    idx = np.asarray(idx).reshape(-1).astype(np.int64)
    vals = np.asarray(vals, np.float32).reshape(-1)
    valid = idx >= 0
    if valid.any():
        np.add.at(out, idx[valid], vals[valid])
    return out
