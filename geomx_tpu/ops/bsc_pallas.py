"""Fused Bi-Sparse (BSC) compression Pallas kernels.

Two kernels replace the dc-tier sparse hot path that a builder's capture
(BENCH_CAPTURED_r05) showed costing more chip time than the wire bytes
it saved:

``bsc_select_pack``
    One fused pass over the gradient bucket that computes the DGC-style
    momentum correction ``u' = 0.9*u + g; v' = v + u'``, applies the
    sampled magnitude boundary, emits the fixed-``k`` (value, index)
    wire pairs, and zeroes the error-feedback buffers at the emitted
    coordinates — everything the unfused XLA graph spreads over a
    mask+cumsum+scatter chain of ~6 HBM-materialized intermediates
    (``ops/sampled_topk.py``).  Bit-exact with that jnp reference:
    identical values, indices (including the -1 sentinel padding and the
    first-k-in-index-order tie rule), and residuals.

``bsc_scatter_add``
    The decompress: accumulates all parties' gathered (value, index)
    pairs into the dense bucket without materializing a per-party dense
    intermediate or an XLA scatter, in work proportional to the pairs
    plus the output, not to their product (see below).

Algorithm notes (select/pack).  The reference scan's two-tier rule
(strictly-above-boundary elements claim slots first, boundary ties queue
after *all* primaries — ``sampled_threshold_select``) needs the total
primary count before any tie's slot is known, so the kernel runs a
2-pass sequential grid over [8, 128] fp32 blocks: pass 0 emits the
primary runs while accumulating the primary count in SMEM, pass 1 emits
the tie runs offset by that total.  Within a block, element ranks come
from matmul prefix-sums (lane-triangular [128,128] + row-triangular
[8,8] — Mosaic has no native cumsum).  The (value, index) outputs are
lane-dense [rows, 128] slabs that stay VMEM-resident across the grid (a
[k, 1] column cannot be sliced at an element offset on the chip: TPU
refs are whole (8, 128) tiles), and each block's kept elements are
placed straight into slab coordinates by two one-hot matmuls per row.
Because every block's emitted ranks are consecutive, runs tile the
output exactly; slots no run covers keep the sentinel pair the slabs
are initialized with at the first grid step.

Wire-format stability: the fused kernel and the jnp reference emit
byte-identical payloads (primaries in ascending index order, then ties,
then -1/0.0 sentinel padding), so parties may mix fused and unfused
paths in one job and checkpointed error-feedback state is
interchangeable between them.

VMEM budget: 3 input + 2 output [8,128] fp32 blocks per grid step
(~20 KB), a few [128,128] / [16,128] one-hots, and the two resident
output slabs — 8 x (k + 2048) bytes, double-buffered, which bounds k
(``MAX_FUSED_K``); above it the kernel raises.

Algorithm notes (decompress).  The output is cut into blocks of
``_OUT_ROWS`` x 128 elements and the pairs, sorted by index (one
``lax.sort`` of the m pairs; sentinels last), into chunks of ``_CHUNK``.
Sorted, chunk c holds pairs for blocks lo_c..hi_c with hi_c <= lo_(c+1),
so the (block, chunk) meetings that do any work form a staircase of at
most ``blocks + chunks`` visits.  ``scatter_visits`` computes that list
in XLA from each chunk's first and last key, and the kernel's 1-D grid
walks it: the two visit lists are scalar-prefetch operands that the
BlockSpecs' index maps read, so Pallas streams each chunk and writes
each output block once, and a visit is one one-hot matmul.  Cost in
grid steps: ``ceil(n / 16384) + ceil(m / 512)``, e.g. 1,908 + 611 for
BERT-large's token embedding (n = 31,254,528, m = 312,546); the grid
this replaced visited every block once per chunk, 1,165,788 steps for
that bucket and 856 ms of a 2.6 s step, 99% of them finding nothing to
do.  A bucket of one block or one chunk needs no order and no schedule
(every block meets every chunk; the product is the sum) and takes
neither.

Index arithmetic is int32 throughout: buckets are limited to 2**31-1
elements.  Buckets are as large as the largest leaf: the bucketing
default is 1 Mi elements of capacity, but a leaf above that gets a
bucket of its own (``compression/bucketing.py``), 31 M elements for
BERT-large's embedding.  Size a kernel's schedule for that, not for the
default.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

MOMENTUM = 0.9  # gc.cc:200 — must match compression/bisparse.py

_LANES = 128
_BLK_ROWS = 8                      # one fp32 tile of rows per grid step
_BLK = _BLK_ROWS * _LANES          # 1024 elements per grid step
_WIN_ROWS = 2 * _BLK_ROWS           # output rows one emitted run can touch
MAX_FUSED_K = 1 << 19               # output pairs held in VMEM (2 x 2 MiB)
_CHUNK = 512                       # (value, index) pairs per decompress step
_OUT_ROWS = 128                    # dense output rows per decompress block
_SENTINEL_KEY = 2 ** 31 - 1        # a sentinel pair's sort key: after every index


def fused_kernels_enabled() -> bool:
    """Master gate for the fused compression kernels: on when the default
    backend is a TPU unless ``GEOMX_FUSED_KERNELS=0`` opts out (the
    shared TPU-fast-path policy, compression/base.default_on_tpu).  The
    jnp reference paths stay bit-exact on every backend and serve as the
    parity oracle (tests/test_bsc_pallas.py)."""
    from geomx_tpu.compression.base import default_on_tpu
    return default_on_tpu("GEOMX_FUSED_KERNELS")


def sampled_boundary_guv(g: jax.Array, u: jax.Array, v: jax.Array, k,
                         sample: int = 8192):
    """The sampled magnitude boundary computed WITHOUT materializing the
    dense momentum-corrected tensor: gathers the ~``sample`` probe
    positions of g/u/v and applies the momentum arithmetic to just those
    — the full ``|v + (0.9u + g)|`` lives only inside the fused kernel.
    Same quantile rule as ``ops.sampled_topk.sampled_boundary``; ``k``
    may be a traced scalar (the control plane's effective-k operand) —
    the boundary position becomes a traced gather index, the kernel's
    static shapes never change."""
    from geomx_tpu.ops.sampled_topk import boundary_position, sample_positions

    n = g.shape[0]
    pos = jnp.asarray(sample_positions(n, sample), jnp.int32)
    samp = jnp.abs(v[pos] + (u[pos] * MOMENTUM + g[pos]))
    m = samp.shape[0]
    ssorted = jnp.sort(samp)
    return ssorted[boundary_position(m, k, n)]


def _ex_cumsum_flat(mask):
    """Exclusive prefix count of ``mask`` [8, 128] in row-major (flat
    index) order, as int32.  Mosaic lowers no cumsum primitive; the
    standard TPU spelling is a pair of triangular matmuls (lane-level
    [128,128], then row offsets via a strictly-lower [8,8])."""
    m = mask.astype(jnp.float32)
    lane_lt = (jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 0)
               < jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 1)
               ).astype(jnp.float32)
    ex_lane = jax.lax.dot_general(m, lane_lt, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    rowtot = jnp.sum(m, axis=1, keepdims=True)                     # [8, 1]
    row_gt = (jax.lax.broadcasted_iota(jnp.int32, (_BLK_ROWS, _BLK_ROWS), 1)
              < jax.lax.broadcasted_iota(jnp.int32, (_BLK_ROWS, _BLK_ROWS), 0)
              ).astype(jnp.float32)
    ex_row = jax.lax.dot_general(row_gt, rowtot, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    return (ex_lane + ex_row).astype(jnp.int32)


def _select_kernel(k, n, g_ref, u_ref, v_ref, thr_ref,
                   newu_ref, newv_ref, vals_ref, idx_ref, cnt):
    """Grid (2, nblocks): pass 0 emits primary (> thr) runs, pass 1 emits
    tie (== thr) runs and the final error-feedback zeroing.  SMEM ``cnt``:
    [0] = running primary count (pass 0; frozen total during pass 1),
    [1] = pass-1 primary re-count, [2] = running tie count."""
    import jax.experimental.pallas as pl

    pas = pl.program_id(0)
    blk = pl.program_id(1)
    thr = thr_ref[0, 0]
    u2 = u_ref[:] * MOMENTUM + g_ref[:]
    v2 = v_ref[:] + u2
    absv = jnp.abs(v2)
    base = blk * _BLK
    flat = base + (
        jax.lax.broadcasted_iota(jnp.int32, (_BLK_ROWS, _LANES), 0) * _LANES
        + jax.lax.broadcasted_iota(jnp.int32, (_BLK_ROWS, _LANES), 1))
    valid = flat < n  # zero padding must not claim tie slots when thr == 0
    primary = (absv > thr) & valid
    secondary = (absv == thr) & valid
    p_rank = _ex_cumsum_flat(primary)
    s_rank = _ex_cumsum_flat(secondary)
    # counts reduce in f32 (exact up to the 1024-element block; Mosaic
    # implements no integer reductions)
    p_cnt = jnp.sum(primary.astype(jnp.float32)).astype(jnp.int32)
    s_cnt = jnp.sum(secondary.astype(jnp.float32)).astype(jnp.int32)

    def emit(emit_mask, rank_local, start):
        """Compact the block's emitted class (local ranks are consecutive
        from 0) into the (value, index) run that owns output slots
        [start, start + count).  The outputs stay VMEM-resident as
        lane-dense [rows, 128] slabs, so the run is built directly in
        slab coordinates — target slot t -> (t // 128, t % 128) within
        the 16-row window that starts at the 8-row tile holding ``start``
        — by two one-hot matmuls per block row, and merged into the
        window where a slot was hit.  Slots no run hits keep the
        sentinel pair the slabs were initialized with."""
        off = jnp.minimum(start, k)  # blocks past k emit nothing: park
        row0 = pl.multiple_of(off // _BLK * _BLK_ROWS, _BLK_ROWS)
        t = jnp.where(emit_mask, off % _BLK + rank_local, -1)
        trow, tcol = t >> 7, t & (_LANES - 1)  # not emitted: row -1
        win_row = jax.lax.broadcasted_iota(jnp.int32, (_WIN_ROWS, _LANES), 0)
        lane_col = jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 0)
        accv = jnp.zeros((_WIN_ROWS, _LANES), jnp.float32)
        acci = jnp.zeros((_WIN_ROWS, _LANES), jnp.float32)
        for r in range(_BLK_ROWS):
            in_row = win_row == trow[r:r + 1, :]
            in_col = (lane_col == tcol[r:r + 1, :]).astype(jnp.float32)
            # local flat index payload, +1 so "no hit" (0) maps to -1
            loc = (jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
                   + (r * _LANES + 1)).astype(jnp.float32)
            # [16, e] x [128, e] contracted over the row's 128 elements e
            accv = accv + jax.lax.dot_general(
                jnp.where(in_row, v2[r:r + 1, :], 0.0), in_col,
                (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
            acci = acci + jax.lax.dot_general(
                jnp.where(in_row, loc, 0.0), in_col,
                (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
        ai = acci.astype(jnp.int32)
        win = pl.ds(row0, _WIN_ROWS)
        vals_ref[win, :] = jnp.where(ai > 0, accv, vals_ref[win, :])
        idx_ref[win, :] = jnp.where(ai > 0, base + ai - 1, idx_ref[win, :])

    @pl.when((pas == 0) & (blk == 0))
    def _init_outputs():
        vals_ref[:] = jnp.zeros_like(vals_ref)
        idx_ref[:] = jnp.full_like(idx_ref, -1)

    @pl.when((pas == 0) & (blk == 0))
    def _reset_primary_count():
        cnt[0] = 0

    @pl.when(pas == 0)
    def _emit_primaries():
        p_pre = cnt[0]
        keep_p = primary & (p_pre + p_rank < k)
        # interim EF state (pass 1 rewrites it with the tie zeroing too)
        newu_ref[:] = jnp.where(keep_p, 0.0, u2)
        newv_ref[:] = jnp.where(keep_p, 0.0, v2)
        emit(keep_p, p_rank, p_pre)
        cnt[0] = p_pre + p_cnt

    @pl.when((pas == 1) & (blk == 0))
    def _reset_tie_counts():
        cnt[1] = 0
        cnt[2] = 0

    @pl.when(pas == 1)
    def _emit_ties():
        np_tot = cnt[0]  # total primaries: ties queue after ALL of them
        p_pre = cnt[1]
        s_pre = cnt[2]
        keep_p = primary & (p_pre + p_rank < k)
        keep_s = secondary & (np_tot + s_pre + s_rank < k)
        keep = keep_p | keep_s
        newu_ref[:] = jnp.where(keep, 0.0, u2)
        newv_ref[:] = jnp.where(keep, 0.0, v2)
        emit(keep_s, s_rank, np_tot + s_pre)
        cnt[1] = p_pre + p_cnt
        cnt[2] = s_pre + s_cnt


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def bsc_select_pack(g: jax.Array, u: jax.Array, v: jax.Array,
                    threshold: jax.Array, k: int, interpret: bool = False):
    """Fused momentum + sampled-boundary select + fixed-k pack + EF reset.

    Args: flat fp32 ``g``/``u``/``v`` of equal length ``n``; ``threshold``
    a traced scalar (the sampled magnitude boundary); static ``k``.
    Returns ``(vals[k], idx[k] int32 with -1 sentinels, new_u[n],
    new_v[n])`` — bit-identical to the ``sampled_threshold_select`` +
    error-feedback jnp chain in compression/bisparse.py.
    """
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = g.shape[0]
    k = int(k)
    rows = max(1, -(-n // _LANES))
    rowsp = -(-rows // _BLK_ROWS) * _BLK_ROWS
    pad = rowsp * _LANES - n

    def shape2(x):
        x = x.reshape(-1).astype(jnp.float32)
        if pad:
            x = jnp.concatenate([x, jnp.zeros((pad,), jnp.float32)])
        return x.reshape(rowsp, _LANES)

    # a run starts anywhere in the 8-row tile holding its first slot and
    # is at most one block long: the 16-row window always fits
    krows = (k // _BLK) * _BLK_ROWS + _WIN_ROWS
    if krows * _LANES > MAX_FUSED_K:
        raise ValueError(
            f"bsc_select_pack keeps its k={k} output pairs VMEM-resident "
            f"and takes k up to {MAX_FUSED_K - _WIN_ROWS * _LANES}; lower "
            "the bucket size or the ratio, or set GEOMX_FUSED_KERNELS=0")
    blk_spec = pl.BlockSpec((_BLK_ROWS, _LANES), lambda p, b: (b, 0))
    out_spec = pl.BlockSpec((krows, _LANES), lambda p, b: (0, 0))
    newu, newv, vals, idx = pl.pallas_call(
        functools.partial(_select_kernel, k, n),
        grid=(2, rowsp // _BLK_ROWS),
        in_specs=[
            blk_spec, blk_spec, blk_spec,                       # g, u, v
            pl.BlockSpec((1, 1), lambda p, b: (0, 0),
                         memory_space=pltpu.SMEM),              # threshold
        ],
        out_specs=(blk_spec, blk_spec,                          # new u, v
                   out_spec, out_spec),                         # vals, idx
        out_shape=(
            jax.ShapeDtypeStruct((rowsp, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((rowsp, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((krows, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((krows, _LANES), jnp.int32),
        ),
        scratch_shapes=[pltpu.SMEM((4,), jnp.int32)],
        interpret=interpret,
    )(shape2(g), shape2(u), shape2(v),
      jnp.asarray(threshold, jnp.float32).reshape(1, 1))
    return (vals.reshape(-1)[:k], idx.reshape(-1)[:k],
            newu.reshape(-1)[:n], newv.reshape(-1)[:n])


def scatter_visits(key: jax.Array, blocks: int, block_elems: int):
    """The decompress's schedule: which (output block, pair chunk) visits
    to make, for ``key`` [chunks, _CHUNK] ascending (sentinels last, as
    INT32_MAX).  Returns ``(blk, chk, total)``: int32 [blocks + chunks]
    visit lists and the number of live visits, at most
    ``blocks + chunks``.

    Chunk c holds pairs for blocks lo_c..hi_c, and lo_c <= hi_c <=
    lo_(c+1) because the keys ascend, so the visits walk a staircase:
    chunk c is paired with blocks lo_c .. max(hi_c, lo_(c+1) - 1) — its
    own, plus the empty blocks up to the next chunk's first, which a
    visit has to zero.  The first chunk starts at block 0, the last
    chunk that holds a pair runs to the last block, a chunk of nothing
    but sentinels gets no visit.  A block's visits are consecutive, so
    the output block stays in VMEM across them and is written once.
    Visits past ``total`` repeat the last block and do nothing."""
    chunks = key.shape[0]
    lo = jnp.minimum(key[:, 0] // block_elems, blocks)
    hi = jnp.minimum(key[:, -1] // block_elems, blocks - 1)
    first = jnp.where(jnp.arange(chunks) == 0, 0, lo)
    last = jnp.maximum(hi, jnp.append(lo[1:], blocks) - 1)
    count = last - first + 1
    end = jnp.cumsum(count)
    t = jnp.arange(blocks + chunks, dtype=jnp.int32)
    # one fused compare-and-count over [visits, chunks]: no loop
    chk = jnp.minimum(
        jnp.searchsorted(end, t, side="right", method="compare_all"),
        chunks - 1).astype(jnp.int32)
    # a chunk's j-th visit is its first block + j: t - (end - count) is j
    blk = jnp.minimum(t + (first - end + count)[chk], blocks - 1)
    return blk.astype(jnp.int32), chk, end[-1:].astype(jnp.int32)


def _scatter_kernel(out_rows, blk_ref, chk_ref, total_ref,
                    vals_ref, key_ref, out_ref):
    """One visit of the schedule: pair chunk ``chk[t]`` [1, _CHUNK]
    against output block ``blk[t]`` [out_rows, 128].  The scatter-add is
    two one-hot compares and one MXU matmul, ``out[r, l] += sum_p
    (row_p == r) * v_p * (col_p == l)`` — exact scatter-add semantics, no
    XLA scatter, no per-party dense buffer.  Keys of other blocks and the
    sentinels' INT32_MAX give a row outside the block and match
    nothing."""
    import jax.experimental.pallas as pl

    t = pl.program_id(0)
    blk = blk_ref[t]

    @pl.when((t == 0) | (blk != blk_ref[jnp.maximum(t - 1, 0)]))
    def _zero_output_block():
        out_ref[:] = jnp.zeros_like(out_ref)

    @pl.when(t < total_ref[0])
    def _scatter_chunk():
        local = key_ref[:] - blk * (out_rows * _LANES)          # [1, S]
        row, col = local >> 7, local & (_LANES - 1)
        a = jnp.where(row == jax.lax.broadcasted_iota(
            jnp.int32, (out_rows, _CHUNK), 0), vals_ref[:], 0.0)
        b = (col == jax.lax.broadcasted_iota(
            jnp.int32, (_LANES, _CHUNK), 0)).astype(jnp.float32)
        # HIGHEST: the MXU's default rounds the fp32 values in ``a`` to
        # bf16 (seen on a v5e: every reconstructed value off by up to
        # 2.9e-3 relative); the full-precision passes make value x 1.0
        # exact, which is what "scatter-add" promises
        out_ref[:] += jax.lax.dot_general(
            a, b, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def bsc_scatter_add(vals: jax.Array, idx: jax.Array, n: int,
                    interpret: bool = False) -> jax.Array:
    """Fused dense reconstruction: scatter-add (value, index) pairs into
    a flat fp32 vector of length ``n``.  Negative indices are sentinel
    padding and contribute nothing; colliding indices accumulate (the
    all-parties aggregate of compression/bisparse.py's decompress).  The
    pairs may come in any order."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m = vals.shape[0]
    chunks = max(1, -(-m // _CHUNK))
    # whole (8, 128) tiles; the last output block may hang over the end
    # (Pallas masks what it writes there), so a bucket of whole tiles,
    # which every large one is, comes back without a copy
    rows = -(-max(n, 1) // _BLK) * _BLK_ROWS
    out_rows = min(_OUT_ROWS, rows)
    blocks = -(-rows // out_rows)
    idx = idx.astype(jnp.int32)
    key = jnp.where(idx < 0, _SENTINEL_KEY, idx)
    vals = vals.astype(jnp.float32)
    if chunks * _CHUNK != m:
        pad = (0, chunks * _CHUNK - m)
        key = jnp.pad(key, pad, constant_values=_SENTINEL_KEY)
        vals = jnp.pad(vals, pad)
    if blocks == 1 or chunks == 1:
        # every block meets every chunk and the product is the sum: the
        # schedule is static and the pairs' order does not matter
        t = jnp.arange(blocks * chunks, dtype=jnp.int32)
        blk, chk = t // chunks, t % chunks
        total = jnp.full((1,), blocks * chunks, jnp.int32)
    else:
        # one ascending order, whatever came: a party's primaries then
        # its ties, several parties' runs, `lax.top_k`'s order of
        # magnitude.  A sort of the m pairs, never of anything the
        # bucket's size; on the chip it is a tenth of this function's
        # time (PERF.md, PR 25), so pairs already in order pay it too
        key, vals = jax.lax.sort((key, vals), num_keys=1, is_stable=False)
        blk, chk, total = scatter_visits(
            key.reshape(chunks, _CHUNK), blocks, out_rows * _LANES)
    pairs = pl.BlockSpec((None, 1, _CHUNK),
                         lambda t, blk, chk, total: (chk[t], 0, 0))
    out = pl.pallas_call(
        functools.partial(_scatter_kernel, out_rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(blk.shape[0],),
            in_specs=[pairs, pairs],
            out_specs=pl.BlockSpec(
                (out_rows, _LANES), lambda t, blk, chk, total: (blk[t], 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, _LANES), jnp.float32),
        interpret=interpret,
    )(blk, chk, total, vals.reshape(chunks, 1, _CHUNK),
      key.reshape(chunks, 1, _CHUNK))
    return out.reshape(-1)[:n]
