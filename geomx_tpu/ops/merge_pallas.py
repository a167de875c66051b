"""Sorted-index segment-sum merge kernel — the compressed-domain merge.

The homomorphic aggregation path (compression/sparseagg.py,
docs/performance.md "Compressed-domain aggregation") needs one core
primitive: merge N parties' (value, index) pair streams **by index**
without materializing anything dense — the segment sum over the
index-sorted pair sequence.  This module owns that primitive in two
bit-identical forms:

``merge_sorted_pairs``
    jnp reference: a fixed binary combining tree over the sorted
    sequence.  Because float addition is not associative, the merge is
    DEFINED as this tree — ``rounds = ceil(log2(max_duplicates))``
    passes in which the element at in-segment rank ``s`` with
    ``s % 2^(r+1) == 0`` absorbs its neighbour at rank ``s + 2^r``
    (duplicates of one index are contiguous after the sort, so the
    neighbour test is one shifted index compare).  Every path — jnp,
    Pallas, and any future backend — must realize exactly this tree,
    which is what makes the merged bits independent of which engine ran
    them.

``merge_sorted_pairs`` with ``fused=True``
    The Pallas form: a grid over lane-dense ``[512, 128]`` blocks of the
    pair stream, each read with a one-tile halo of the pairs that follow
    it, applying the same ``rounds`` shifted combines in registers and
    extracting the per-segment totals at head positions.  Interpret
    mode is the CPU parity oracle.

Output format: same length as the input, the total of each index
segment at its FIRST (head) position, sentinel ``(0.0, -1)`` everywhere
else — a valid sparse stream the re-selection stage consumes directly.
Sentinel input pairs (index ``INT32_MAX`` after the sort's key mapping)
never combine and come out as sentinels.

VMEM budget: three input blocks, three halo tiles and two output
blocks of 256 KiB each (double-buffered), whatever the stream length.
The tree reads ``2**rounds - 1`` pairs ahead, which must fit the
1024-pair halo: at most 1024 contributions per index.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# post-sort sentinel key: real indices are < 2**31 - 1 (int32 buckets)
SENTINEL_KEY = 2**31 - 1

_LANES = 128
_BLOCK_ROWS = 512   # pairs per grid step: 512 x 128 = 64 Ki
_HALO_ROWS = 8      # one fp32 tile of look-ahead past each block


def merge_rounds(max_duplicates: int) -> int:
    """Combining-tree depth for segments of at most ``max_duplicates``
    entries (one contribution per party => the dc axis size)."""
    r = 0
    while (1 << r) < max(1, int(max_duplicates)):
        r += 1
    return r


def sort_pairs(vals: jax.Array, idx: jax.Array):
    """Canonicalize a pair stream for the merge: map ``-1`` sentinels to
    ``SENTINEL_KEY`` (so they sort last) and stable-sort by index.  The
    stable order makes the combining tree's operand order — and hence
    the merged BITS — a function of the pair multiset alone, not of the
    arrival/buffer order the caller happened to hold them in, provided
    the caller presents pairs in a canonical pre-order (party rank)."""
    key = jnp.where(idx >= 0, idx, SENTINEL_KEY).astype(jnp.int32)
    order = jnp.argsort(key, stable=True)
    return vals[order], key[order]


def segment_ranks(skey: jax.Array):
    """(rank-within-segment, head mask) for a sorted key column —
    integer arithmetic only (cummax of int32), so it is exact and
    shared verbatim by both merge paths."""
    m = skey.shape[0]
    pos = jnp.arange(m, dtype=jnp.int32)
    prev = jnp.concatenate([jnp.full((1,), -2, jnp.int32), skey[:-1]])
    head = skey != prev
    seg_start = jax.lax.cummax(jnp.where(head, pos, 0))
    return pos - seg_start, head


def _merge_tree_ref(svals, skey, rank, rounds: int):
    """The defining combining tree (jnp reference path)."""
    v = svals
    for r in range(rounds):
        d = 1 << r
        pv = jnp.concatenate([v[d:], jnp.zeros((d,), v.dtype)])
        pk = jnp.concatenate(
            [skey[d:], jnp.full((d,), SENTINEL_KEY, jnp.int32)])
        take = (pk == skey) & (skey != SENTINEL_KEY) & (rank % (2 * d) == 0)
        v = jnp.where(take, v + pv, v)
    head = (rank == 0) & (skey != SENTINEL_KEY)
    return (jnp.where(head, v, 0.0),
            jnp.where(head, skey, -1).astype(jnp.int32))


def _merge_kernel(rounds: int, v_ref, vh_ref, k_ref, kh_ref, g_ref, gh_ref,
                  outv_ref, outi_ref):
    """One block of the pair stream plus a one-tile halo of what follows
    it: the same combining tree as :func:`_merge_tree_ref`, with the
    shifted neighbour reads realized as row-major rotates of the
    lane-dense window.  A rotate wraps the window's last ``d`` entries,
    so after all rounds the last ``2**rounds - 1`` entries are garbage —
    inside the halo, which is not written."""
    from geomx_tpu.ops.bucket_pallas import flat_roll

    v = jnp.concatenate([v_ref[:], vh_ref[:]])
    key = jnp.concatenate([k_ref[:], kh_ref[:]])
    rank = jnp.concatenate([g_ref[:], gh_ref[:]])
    size = v.shape[0] * _LANES
    live = key != SENTINEL_KEY
    for r in range(rounds):
        d = 1 << r
        pv = flat_roll(v, size - d)       # pv.flat[f] = v.flat[f + d]
        pk = flat_roll(key, size - d)
        take = (pk == key) & live & ((rank & (2 * d - 1)) == 0)
        v = jnp.where(take, v + pv, v)
    head = (rank == 0) & live
    rows = outv_ref.shape[0]
    outv_ref[:] = jnp.where(head, v, 0.0)[:rows]
    outi_ref[:] = jnp.where(head, key, -1)[:rows]


@functools.partial(jax.jit, static_argnames=("rounds", "interpret"))
def _merge_tree_pallas(svals, skey, rank, rounds: int,
                       interpret: bool = False):
    import jax.experimental.pallas as pl

    if (1 << rounds) > _HALO_ROWS * _LANES:
        raise ValueError(
            f"the fused merge reads {(1 << rounds) - 1} pairs ahead and "
            f"its halo holds {_HALO_ROWS * _LANES}: at most "
            f"{_HALO_ROWS * _LANES} contributions per index")
    m = svals.shape[0]
    rows = -(-m // _LANES)
    block = min(_BLOCK_ROWS, -(-rows // _HALO_ROWS) * _HALO_ROWS)
    nblocks = -(-rows // block)
    padded = (nblocks * block + _HALO_ROWS) * _LANES

    def slab(x, fill, dtype):
        x = jnp.pad(x.astype(dtype), (0, padded - m), constant_values=fill)
        return x.reshape(-1, _LANES)

    blk = pl.BlockSpec((block, _LANES), lambda i: (i, 0))
    halo = pl.BlockSpec((_HALO_ROWS, _LANES),
                        lambda i: ((i + 1) * (block // _HALO_ROWS), 0))
    v, k, g = (slab(svals, 0.0, jnp.float32),
               slab(skey, SENTINEL_KEY, jnp.int32), slab(rank, 0, jnp.int32))
    outv, outi = pl.pallas_call(
        functools.partial(_merge_kernel, rounds),
        grid=(nblocks,),
        in_specs=[blk, halo, blk, halo, blk, halo],
        out_specs=(blk, blk),
        out_shape=(
            jax.ShapeDtypeStruct((nblocks * block, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((nblocks * block, _LANES), jnp.int32)),
        interpret=interpret,
    )(v, v, k, k, g, g)
    return outv.reshape(-1)[:m], outi.reshape(-1)[:m]


def merge_sorted_pairs(vals: jax.Array, idx: jax.Array, max_duplicates: int,
                       fused: bool = False, interpret: bool = False):
    """Merge a (value, index) pair stream by index.

    ``vals``/``idx`` need NOT be pre-sorted — the canonical stable sort
    by index runs here (XLA, shared by both paths), then the combining
    tree realizes the segment sums.  ``max_duplicates`` bounds how many
    pairs can share one index (the dc axis size: each party contributes
    an index at most once).  Returns ``(merged_vals, merged_idx)`` of
    the SAME length: segment totals at head positions, ``(0.0, -1)``
    sentinels elsewhere.  ``fused=True`` runs the Pallas kernel
    (``interpret=True`` for CPU parity) — bit-identical to the jnp path
    by construction (same sort, same tree).
    """
    svals, skey = sort_pairs(vals.astype(jnp.float32),
                             idx.astype(jnp.int32))
    rank, _head = segment_ranks(skey)
    rounds = merge_rounds(max_duplicates)
    if fused:
        return _merge_tree_pallas(svals, skey, rank, rounds,
                                  interpret=interpret)
    return _merge_tree_ref(svals, skey, rank, rounds)
