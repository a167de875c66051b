"""Fused Bi-Sparse (BSC) compression Pallas kernels.

Three kernels replace the dc-tier sparse hot path whose unfused form
cost more chip time than the wire bytes it saved:

``bsc_boundary_probe``
    The boundary's samples: the momentum-corrected magnitudes at the
    probe's static positions, taken while g, u and v stream by once, in
    place of three XLA gathers that pay 8.6 ns an index whatever the
    bucket's size (0.21 ms a bucket, 22.7 ms of a 214.7 ms BERT-large
    step: PERF.md, PR 32).  Bit-exact with the gathered samples.

``bsc_select_pack``
    Computes the DGC-style momentum correction ``u' = 0.9*u + g; v' = v +
    u'``, applies the sampled magnitude boundary, emits the fixed-``k``
    (value, index) wire pairs, and zeroes the error-feedback buffers at
    the emitted coordinates — everything the unfused XLA graph spreads
    over a mask+cumsum+scatter chain of ~6 HBM-materialized
    intermediates (``ops/sampled_topk.py``).  Bit-exact with that jnp
    reference: identical values, indices (including the -1 sentinel
    padding and the first-k-in-index-order tie rule), and residuals.

``bsc_scatter_add``
    The decompress: accumulates all parties' gathered (value, index)
    pairs into the dense bucket without materializing a per-party dense
    intermediate or an XLA scatter, in work proportional to the pairs
    plus the output, not to their product (see below).

Algorithm notes (select/pack): count first, then place by a schedule.
The reference scan's two-tier rule (strictly-above-boundary elements
claim slots first, boundary ties queue after *all* primaries —
``sampled_threshold_select``) makes a pair's slot a prefix sum over the
whole bucket.  So the bucket is cut into tiles of ``_TILE_ROWS`` x 128
elements and goes through two kernels.  ``bsc_select_pack_count`` does
the momentum arithmetic and counts each tile's primaries and ties.  XLA
turns the ``2 * tiles`` counts into first slots (one exclusive prefix
sum: the primaries' tiles, then the ties') and into the placement's
schedule (``place_visits``): slots ascend with the tiles inside a class,
so the (tile, output block) meetings that hold a pair form a staircase
of at most ``tiles + out_blocks`` visits a class.  ``bsc_select_pack_
place`` walks it on a 1-D grid, the visit lists scalar-prefetch operands
that the BlockSpecs' index maps read: a tile streams in once for its
primaries and once more only if it holds a tie that gets a slot, each
output block of ``_PAIR_ROWS`` x 128 pairs is written once, nothing
stays resident, and k has no limit.  A tile's
first visit writes its new u and v (what to zero is decided from the
prefix sums: all of a class, none of it, or by rank in the one tile slot
k falls in) and compacts the class it places; a tile with no pair of the
class pays neither ranks nor compaction, so ties cost what ties there
are.  Within a tile, ranks come from matmul prefix-sums (lane-triangular
[128,128] + row-triangular [rows,rows] on 0/1 operands — Mosaic has no
native cumsum), and the kept elements move to their consecutive slots by
``_compact``: log2(tile) whole-frame rolls with three selects each, no
one-hot and no value through the MXU, the same work at any density.  The
(value, index) outputs are lane-dense [rows, 128] (a [k, 1] column
cannot be sliced at an element offset on the chip: TPU refs are whole
(8, 128) tiles); the compaction lands a tile's run at the lane and
sublane its first slot has in its output tile, so placing is an aligned
row-window merge.  Slots no run covers keep the sentinel pair every
output block starts from.  A bucket of one tile needs no schedule and no
prefix sum and takes neither: one call of one kernel does all of the
above with the output slabs whole in VMEM.

Wire-format stability: the fused kernel and the jnp reference emit
byte-identical payloads (primaries in ascending index order, then ties,
then -1/0.0 sentinel padding), so parties may mix fused and unfused
paths in one job and checkpointed error-feedback state is
interchangeable between them.

VMEM budget (placing pass): 3 input + 2 output [256,128] fp32 tiles and
two [64,128] output blocks per grid step, double-buffered (~1.4 MB), the
[392,128] value and index frames (~0.4 MB) and a few frame-sized
temporaries of the compaction.  The visit lists and first slots are int32
in SMEM: 4 x (5 tiles + 2 out_blocks) bytes.  Boundary probe: 3 input
[256,128] fp32 tiles, double-buffered (0.8 MB), the position and sample
slabs (2 x 32 KB, resident), the tile's magnitudes and their three bf16
pieces (0.3 MB) and one [256,128] product: under 1.5 MB.  Two int32 a tile
in SMEM.

Algorithm notes (boundary probe).  The positions are static
(``sampled_topk.sample_positions``), so the host sorts them at trace time
and the kernel gets them as one [m / 128, 128] int32 slab, ascending, that
stays in VMEM beside the [m / 128, 128] float32 slab of samples it fills:
sample s of the sorted order lands at row s // 128, lane s % 128 (position
order, not Weyl order; the sort that follows makes that invisible).  A 1-D
grid walks the bucket in the select/pack's tiles.  A step computes ``|v +
(u * MOMENTUM + g)|`` on its tile, in the order of operations of
``sampled_boundary_guv``'s line, and then visits the slab rows that hold
one of its positions (first row and count are scalar-prefetch lists: 1 or
2 rows a tile at 4 M elements, 3 at 1 M, all 64 for a bucket of one tile).
A visit is the decompress's one-hot product the other way round: the
row's 128 positions give a [128, 128] one-hot of their lanes, ``tile @
one-hot`` brings each position's lane to its slab lane in every tile row,
a row mask and a sublane sum pick the tile row.  The tile goes through
the MXU as three bf16 pieces that sum to it exactly (``x = hi + mid +
lo``, 8 + 8 + 8 significand bits, split once a tile; the one-hot is exact
in bf16), three passes where ``Precision.HIGHEST`` would make six and
split again at every visit; each product is one value times 1.0 plus
zeros, and ``(hi + mid) + lo`` is exact in float32.  Mosaic loads and
stores no single row at a dynamic, unaligned sublane, so a slab row is
read and merged through the aligned window of eight rows that holds it.
Cost: the bucket's 12 n bytes once and one grid step a tile, so above
``_PROBE_GATHER_ABOVE`` elements the three gathers are cheaper and stay
(``bsc_sampled_boundary``); a bucket no larger than the probe IS its
sample and needs no fetch at all.

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
_BLK_ROWS = 8                      # one fp32 (8, 128) tile of rows
_BLK = _BLK_ROWS * _LANES          # 1024 elements
_TILE_ROWS = 256                   # select/pack: rows per dense grid step
_TILE = _TILE_ROWS * _LANES        # 32768 elements
_PAIR_ROWS = 64                    # select/pack: output rows per block
_CHUNK = 512                       # (value, index) pairs per decompress step
_OUT_ROWS = 128                    # dense output rows per decompress block
_SENTINEL_KEY = 2 ** 31 - 1        # a sentinel pair's sort key: after every index
# boundary probe: above this many elements three gathers of 8,192 indices
# cost less than streaming the bucket's 12 n bytes.  tools/
# boundary_timing.py on a v5e (PERF.md section 5, PR 32): gathers 0.186 ms
# a call at 4,194,304 and at 8,388,608 elements alike, the kernel 0.078
# and 0.310 (past ~4 M the three operands no longer stay in fast memory
# and a tile costs twice as much); between them the lines cross near 5.9 M
_PROBE_GATHER_ABOVE = 6 * 1024 * 1024


def sampled_boundary_guv(g: jax.Array, u: jax.Array, v: jax.Array, k,
                         sample: int = 8192):
    """The sampled magnitude boundary: the (1 - k/n) quantile of the
    sorted momentum-corrected magnitudes at ~``sample`` probe positions,
    computed WITHOUT materializing the dense tensor: it gathers g/u/v at
    the probe positions (``sampled_topk.sample_positions``) and applies
    the momentum arithmetic to just those.  The one boundary of every
    path: the kernel and the jnp scan select against the same scalar.
    ``k`` may be a traced scalar (the control plane's effective-k
    operand) — the boundary position becomes a traced gather index, no
    shape changes."""
    from geomx_tpu.ops.sampled_topk import boundary_position, sample_positions

    n = g.shape[0]
    pos = jnp.asarray(sample_positions(n, sample), jnp.int32)
    samp = jnp.abs(v[pos] + (u[pos] * MOMENTUM + g[pos]))
    m = samp.shape[0]
    ssorted = jnp.sort(samp)
    return ssorted[boundary_position(m, k, n)]


@functools.lru_cache(maxsize=None)
def probe_plan(n: int, sample: int = 8192):
    """The probe kernel's static schedule for a bucket of ``n`` elements:
    ``(positions, first_row, row_count)``.  ``positions`` is the probe's
    positions ascending, int32 [m / 128, 128]; a tile's are consecutive
    there, so it fills slab rows ``first_row[t]`` to ``first_row[t] +
    row_count[t] - 1`` (a row two tiles share is visited by both; a tile
    that holds no position visits none)."""
    import numpy as np
    from geomx_tpu.ops.sampled_topk import sample_positions

    pos = np.sort(sample_positions(n, sample))
    tile = pos // _TILE
    tiles = -(-n // _TILE)
    first = np.zeros((tiles,), np.int32)
    count = np.zeros((tiles,), np.int32)
    held = np.unique(tile)
    lo = np.searchsorted(tile, held, side="left") // _LANES
    hi = (np.searchsorted(tile, held, side="right") - 1) // _LANES
    first[held], count[held] = lo, hi - lo + 1
    return pos.reshape(-1, _LANES).astype(np.int32), first, count


def _probe_kernel(n, first_ref, count_ref, pos_ref, g_ref, u_ref, v_ref,
                  out_ref):
    """One tile of the boundary probe: its momentum-corrected magnitudes,
    then one visit for every slab row that holds one of its positions (see
    the module docstring)."""
    import jax.experimental.pallas as pl

    t = pl.program_id(0)
    rows = g_ref.shape[0]
    x = jnp.abs(v_ref[:] + (u_ref[:] * MOMENTUM + g_ref[:]))
    if n % (rows * _LANES):
        # no position is >= n, but the products below read the whole
        # tile and 0 x whatever hangs over the end need not be 0
        x = jnp.where(t * _TILE + _local_index(rows) < n, x, 0.0)
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    lane_of = jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 0)
    row_of = jax.lax.broadcasted_iota(jnp.int32, (rows, _LANES), 0)
    sub_of = jax.lax.broadcasted_iota(jnp.int32, (_BLK_ROWS, _LANES), 0)

    def to_slab_lanes(piece, pick):
        return jax.lax.dot_general(piece, pick, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    def visit(i, carry):
        r = first_ref[t] + i
        window = pl.ds(pl.multiple_of(r // _BLK_ROWS * _BLK_ROWS, _BLK_ROWS),
                       _BLK_ROWS)
        mine = sub_of == r % _BLK_ROWS
        local = pos_ref[window, :] - t * _TILE
        local = jnp.where(mine & (local >= 0) & (local < _TILE), local, -1)
        # slab row r alone, as [1, 128]: every other entry is -1, so the
        # maximum is it (in float32, exact below 2**24: Mosaic reduces no
        # integers); -1 where the position is another tile's
        loc = jnp.max(local.astype(jnp.float32), axis=0,
                      keepdims=True).astype(jnp.int32)
        pick = (lane_of == (loc & (_LANES - 1))).astype(jnp.bfloat16)
        inrow = ((to_slab_lanes(hi, pick) + to_slab_lanes(mid, pick))
                 + to_slab_lanes(lo, pick))
        z = jnp.sum(jnp.where(row_of == (loc >> 7), inrow, 0.0),
                    axis=0, keepdims=True)
        out_ref[window, :] = jnp.where(mine & (loc >= 0), z,
                                       out_ref[window, :])
        return carry

    jax.lax.fori_loop(0, count_ref[t], visit, 0)


@functools.partial(jax.jit, static_argnames=("sample", "interpret"))
def bsc_boundary_probe(g: jax.Array, u: jax.Array, v: jax.Array,
                       sample: int = 8192, interpret: bool = False):
    """The boundary's ``sample`` samples of a bucket larger than that,
    ``|v + (u * MOMENTUM + g)|`` at ``sample_positions(n, sample)``, in
    ascending order of position, from one streamed pass over g, u and v.
    Each equals the gathered sample bit for bit where the 128-element row
    around it is finite: the one-hot products make 0 x inf a NaN, so a
    non-finite element (or one above 3.39e38, bf16's largest) turns the
    samples of its own row to NaN, where the gathers let only a sampled
    element reach the boundary.  A magnitude under 2**-102 has a bf16
    piece that is subnormal, which the chip flushes to 0."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = g.shape[0]
    pos, first, count = probe_plan(n, sample)
    tiles = first.shape[0]
    operands = [_tile_rows(x, n, tiles) for x in (g, u, v)]
    rows = min(_TILE_ROWS, operands[0].shape[0])
    tile_spec = pl.BlockSpec((rows, _LANES), lambda t, first, count: (t, 0))
    slab = pl.BlockSpec(pos.shape, lambda t, first, count: (0, 0))
    out = pl.pallas_call(
        functools.partial(_probe_kernel, n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(tiles,),
            in_specs=[slab, tile_spec, tile_spec, tile_spec],
            out_specs=slab,
        ),
        out_shape=jax.ShapeDtypeStruct(pos.shape, jnp.float32),
        name="bsc_boundary_probe",
        interpret=interpret,
    )(jnp.asarray(first), jnp.asarray(count), jnp.asarray(pos), *operands)
    return out.reshape(-1)


def bsc_sampled_boundary(g: jax.Array, u: jax.Array, v: jax.Array, k,
                         sample: int = 8192,
                         gather_above: int = _PROBE_GATHER_ABOVE,
                         interpret: bool = False):
    """:func:`sampled_boundary_guv` on a TPU: the same float, the samples
    fetched at what the bucket's size makes cheapest.  A bucket no larger
    than the probe is its own sample (the positions are a permutation: the
    multiplier is prime and larger than n): no fetch.  Up to
    ``gather_above`` elements :func:`bsc_boundary_probe` streams the
    bucket once; above it three gathers of ``sample`` indices cost less
    than the bucket's bytes.  The sort, the quantile's position (``k``
    static or traced) and the index are the jnp form's."""
    from geomx_tpu.ops.sampled_topk import boundary_position

    n = g.shape[0]
    m = min(n, int(sample))
    if n > gather_above or (m < n and m % _BLK):
        return sampled_boundary_guv(g, u, v, k, sample)
    if m == n:
        samp = jnp.abs(v + (u * MOMENTUM + g))
    else:
        samp = bsc_boundary_probe(g, u, v, sample=sample, interpret=interpret)
    return jnp.sort(samp)[boundary_position(m, k, n)]


def select_pack_ref(g: jax.Array, u: jax.Array, v: jax.Array,
                    threshold: jax.Array, k: int):
    """jnp form of :func:`bsc_select_pack`, the only path off a TPU and
    the kernel's oracle: momentum correction, the two-tier scan of
    ``sampled_topk.sampled_threshold_select``, error-feedback reset of
    the emitted coordinates (gc.cc:250-252).  Same four outputs."""
    from geomx_tpu.ops.sampled_topk import sampled_threshold_select

    u = u * MOMENTUM + g
    v = v + u
    vals, idx, keep = sampled_threshold_select(v, jnp.abs(v), k, threshold)
    return vals, idx, jnp.where(keep, 0.0, u), jnp.where(keep, 0.0, v)


def scatter_add_ref(vals: jax.Array, idx: jax.Array, n: int) -> jax.Array:
    """jnp form of :func:`bsc_scatter_add` (XLA's scatter-add): bitwise
    equal where no two pairs share an index, to rounding where they do
    (the order of the sum differs)."""
    valid = idx >= 0
    return jnp.zeros((n,), jnp.float32).at[jnp.where(valid, idx, 0)].add(
        jnp.where(valid, vals, 0.0))


def _momentum_classes(g_ref, u_ref, v_ref, thr, base, n):
    """The rule by the element: momentum correction, then the two classes
    a coordinate can be emitted in.  Returns ``(u', v', primary,
    secondary)`` for the block whose first flat index is ``base``."""
    u2 = u_ref[:] * MOMENTUM + g_ref[:]
    v2 = v_ref[:] + u2
    absv = jnp.abs(v2)
    flat = base + _local_index(u2.shape[0])
    # zero padding (and whatever a block that hangs over the end reads)
    # must not claim tie slots when thr == 0
    valid = flat < n
    return u2, v2, (absv > thr) & valid, (absv == thr) & valid


def _local_index(rows):
    return (jax.lax.broadcasted_iota(jnp.int32, (rows, _LANES), 0) * _LANES
            + jax.lax.broadcasted_iota(jnp.int32, (rows, _LANES), 1))


def _count(mask):
    # counts reduce in f32 (exact up to 2**24, a tile is 2**15; Mosaic
    # implements no integer reductions)
    return jnp.sum(mask.astype(jnp.float32)).astype(jnp.int32)


def _ex_rank(mask):
    """Exclusive prefix count of ``mask`` [rows, 128] in row-major (flat
    index) order, as int32.  Mosaic lowers no cumsum primitive; the
    standard TPU spelling is a pair of triangular matmuls (lane-level
    [128,128], then row offsets via a strictly-lower [rows,rows]).  The
    operands are 0/1 and row totals up to 128, exact in the MXU's bf16
    pass with its float32 accumulation: no ``HIGHEST``."""
    rows = mask.shape[0]
    m = mask.astype(jnp.float32)
    lane_lt = (jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 0)
               < jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 1)
               ).astype(jnp.float32)
    ex_lane = jax.lax.dot_general(m, lane_lt, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    rowtot = jnp.broadcast_to(jnp.sum(m, axis=1, keepdims=True),
                              (rows, _LANES))
    row_lt = (jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 1)
              < jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 0)
              ).astype(jnp.float32)
    ex_row = jax.lax.dot_general(row_lt, rowtot, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    return (ex_lane + ex_row).astype(jnp.int32)


def _src_bits(rows):
    """Bits of a tile-local flat index."""
    return max(1, (rows * _LANES - 1).bit_length())


def _flat_roll(x, step):
    """``y[p] = x[(p + step) mod size]`` over the row-major order of
    ``x`` [rows, 128], ``step`` a static power of two."""
    from jax.experimental.pallas import tpu as pltpu

    rows = x.shape[0]
    if step >= _LANES:
        return pltpu.roll(x, rows - step // _LANES, axis=0)
    same_row = pltpu.roll(x, _LANES - step, axis=1)
    next_row = pltpu.roll(same_row, rows - 1, axis=0)
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where(lane < _LANES - step, same_row, next_row)


def _compact(keep, rank, v2, lead):
    """Move a tile's kept elements, in index order, to the consecutive
    flat positions ``lead + rank`` of a frame of ``rows + 8`` rows whose
    row 8 is the tile's first (``lead`` < 1024 is where the tile's first
    pair falls in its (8, 128) output tile, so the frame's rows are the
    output's rows from that tile on).  Returns ``(values, packed)``
    [rows + 8, 128]; ``packed`` is 0 where no pair landed and carries the
    pair's tile-local source index in its low ``_src_bits(rows)`` bits.

    The move is the compress of Hacker's Delight 7-4, by the vreg: a kept
    element has to travel ``d = 1024 + local - lead - rank`` >= 1 places
    toward the front, d never decreases from one kept element to the next
    and grows by at most their distance less one, so taking d's bits
    lowest first (those with bit b set move 2**b places, all at once) no
    two kept elements ever meet, and one that would wrap around has the
    bit clear.  A step is a roll of the whole frame and three selects: no
    one-hot, no matmul, the values never leave float32, and the cost is
    the same at any density."""
    rows = keep.shape[0]
    sbits = _src_bits(rows)
    local = _local_index(rows)
    dist = (_BLK + local - lead) - rank
    head = jnp.zeros((_BLK_ROWS, _LANES), jnp.int32)
    packed = jnp.concatenate(
        [head, jnp.where(keep, (dist << sbits) | local, 0)], axis=0)
    val = jnp.concatenate([head.astype(jnp.float32), v2], axis=0)
    for b in range((_BLK + rows * _LANES - 1).bit_length()):
        bit = 1 << (sbits + b)
        coming_p, coming_v = _flat_roll(packed, 1 << b), _flat_roll(val, 1 << b)
        arrives = (coming_p & bit) != 0
        leaves = (packed & bit) != 0
        packed = jnp.where(arrives, coming_p, jnp.where(leaves, 0, packed))
        val = jnp.where(arrives, coming_v, val)
    return val, packed


def _select_tile_kernel(k, n, g_ref, u_ref, v_ref, thr_ref,
                        newu_ref, newv_ref, vals_ref, idx_ref):
    """A bucket of one tile: the counts, the ranks, the error-feedback
    reset and both classes' placement in one call, the (value, index)
    slabs whole in VMEM.  No schedule and no prefix sum."""
    import jax.experimental.pallas as pl

    rows = g_ref.shape[0]
    u2, v2, primary, secondary = _momentum_classes(
        g_ref, u_ref, v_ref, thr_ref[0, 0], 0, n)
    p_rank, s_rank = _ex_rank(primary), _ex_rank(secondary)
    p_cnt, s_cnt = _count(primary), _count(secondary)
    keep_p = primary & (p_rank < k)
    keep_s = secondary & (p_cnt + s_rank < k)  # ties queue after ALL primaries
    keep = keep_p | keep_s
    newu_ref[:] = jnp.where(keep, 0.0, u2)
    newv_ref[:] = jnp.where(keep, 0.0, v2)
    vals_ref[:] = jnp.zeros_like(vals_ref)
    idx_ref[:] = jnp.full_like(idx_ref, -1)

    def place(kept, rank, start, count):
        @pl.when((count > 0) & (start < k))
        def _():
            val, packed = _compact(kept, rank, v2, start % _BLK)
            win = pl.ds(pl.multiple_of(start // _BLK * _BLK_ROWS, _BLK_ROWS),
                        rows + _BLK_ROWS)
            hit = packed != 0
            vals_ref[win, :] = jnp.where(hit, val, vals_ref[win, :])
            idx_ref[win, :] = jnp.where(
                hit, packed & ((1 << _src_bits(rows)) - 1), idx_ref[win, :])

    place(keep_p, p_rank, 0, p_cnt)
    place(keep_s, s_rank, p_cnt, s_cnt)


def _count_kernel(n, g_ref, u_ref, v_ref, thr_ref, cnt_ref):
    """Pass 1 of a bucket of several tiles: tile t's count of primaries
    and of ties, into lane t of rows 0 and 1 of the one [8, tiles] output
    block, which stays in VMEM across the grid (4 bytes a tile and class
    in HBM, and lane-dense for the prefix sums that read it)."""
    import jax.experimental.pallas as pl

    t = pl.program_id(0)
    _, _, primary, secondary = _momentum_classes(
        g_ref, u_ref, v_ref, thr_ref[0, 0], t * _TILE, n)

    @pl.when(t == 0)
    def _():
        cnt_ref[:] = jnp.zeros_like(cnt_ref)

    row = jax.lax.broadcasted_iota(jnp.int32, cnt_ref.shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, cnt_ref.shape, 1)
    cnt_ref[:] = jnp.where(
        lane == t, jnp.where(row == 0, _count(primary), _count(secondary)),
        cnt_ref[:])


def select_pack_shape(n: int, k: int):
    """``(tiles, out_blocks, out_block_rows)`` of a bucket of ``n``
    elements and ``k`` slots, from shapes alone: the dense passes' grid
    and the (value, index) output's blocks.  One tile means no schedule
    (``bsc_select_pack`` then makes one call whose slabs hold every
    slot)."""
    tiles = max(1, -(-n // _TILE))
    krows = -(-max(int(k), 1) // _BLK) * _BLK_ROWS
    if tiles == 1:
        return 1, 1, krows
    out_rows = min(_PAIR_ROWS, krows)
    return tiles, -(-krows // out_rows), out_rows


def place_visits(p_cnt: jax.Array, s_cnt: jax.Array, k: int,
                 out_blocks: int, block_slots: int):
    """The placement's schedule, from the counting pass's per-tile counts
    of primaries and of ties.  Returns ``(item, blk, total, start)``:
    int32 [2 * tiles + out_blocks] visit lists, the number of live visits
    and the [2 * tiles + 1] first slots.

    An item is a tile in a class: item i < tiles is tile i's primaries,
    item tiles + i its ties, and ``start`` is the exclusive prefix sum
    over the items in that order, which IS the slot rule (primaries in
    index order, then ties).  Slots ascend with the items, so item i
    meets output blocks ``start[i] // block_slots`` to ``(start[i + 1] -
    1) // block_slots`` (cut at slot k) and the (item, block) meetings
    that hold a pair form a staircase.  Every primary item is visited at
    least once, pairs or none, because its visit also writes the tile's
    new u and v; a tie item with no pair below slot k is not visited at
    all; the last live item walks on to the last output block, so every
    block is written (the sentinels).  At most ``tiles + live tie items +
    out_blocks`` visits.  Visits past ``total`` repeat the last live one
    and do nothing."""
    tiles = p_cnt.shape[0]
    items = 2 * tiles
    cnt = jnp.concatenate([p_cnt, s_cnt]).astype(jnp.int32)
    end = jnp.cumsum(cnt)
    start = end - cnt
    lo_slot, hi_slot = jnp.minimum(start, k), jnp.minimum(end, k)
    holds = hi_slot > lo_slot
    i = jnp.arange(items, dtype=jnp.int32)
    live = (i < tiles) | holds
    last_live = jnp.max(jnp.where(live, i, -1))
    lo = jnp.minimum(lo_slot // block_slots, out_blocks - 1)
    hi = jnp.where(holds, (hi_slot - 1) // block_slots, lo)
    hi = jnp.where(i == last_live, out_blocks - 1, hi)
    count = jnp.where(live, hi - lo + 1, 0)
    vend = jnp.cumsum(count)
    t = jnp.arange(items + out_blocks, dtype=jnp.int32)
    # one fused compare-and-count over [visits, items]: no loop
    item = jnp.searchsorted(vend, t, side="right",
                            method="compare_all").astype(jnp.int32)
    dead = t >= vend[-1]
    item = jnp.where(dead, last_live, item)
    # an item's j-th visit is its first block + j: t - (vend - count) is j
    blk = jnp.where(dead, out_blocks - 1, t + (lo - vend + count)[item])
    return (item, blk.astype(jnp.int32), vend[-1:].astype(jnp.int32),
            jnp.append(start, end[-1]).astype(jnp.int32))


def _place_kernel(k, n, tiles, item_ref, blk_ref, total_ref, start_ref,
                  g_ref, u_ref, v_ref, thr_ref,
                  newu_ref, newv_ref, vals_ref, idx_ref,
                  cval, cidx, holds_ref):
    """Pass 2: one visit of the schedule, item ``item[t]`` (a tile in a
    class) against output block ``blk[t]``.  An item's first visit does
    the tile's dense work: new u and v (every coordinate kept in EITHER
    class zeroed, decided from the prefix sums: all of a class, none of
    it, or by rank in the one tile slot k falls in), and, where the item
    holds a pair, the compaction of its class into the ``cval`` /
    ``cidx`` frame, which stays for the item's other visits.  A visit
    then merges the frame's rows that fall in its output block.  A tile
    with no pair of the class pays neither ranks nor compaction."""
    import jax.experimental.pallas as pl

    t = pl.program_id(0)
    item, blk = item_ref[t], blk_ref[t]
    before = jnp.maximum(t - 1, 0)
    live = t < total_ref[0]
    out_rows = vals_ref.shape[0]
    frame = _TILE_ROWS + _BLK_ROWS

    @pl.when(t == 0)
    def _no_pairs_around_the_frame():
        cidx[:] = jnp.full_like(cidx, -1)

    @pl.when((t == 0) | (blk != blk_ref[before]))
    def _sentinels():
        vals_ref[:] = jnp.zeros_like(vals_ref)
        idx_ref[:] = jnp.full_like(idx_ref, -1)

    @pl.when(live & ((t == 0) | (item != item_ref[before])))
    def _tile():
        ties = item >= tiles
        tile = jnp.where(ties, item - tiles, item)
        u2, v2, primary, secondary = _momentum_classes(
            g_ref, u_ref, v_ref, thr_ref[0, 0], tile * _TILE, n)

        def ranks(mask, lo, hi, placed):
            # all of a class kept (rank 0 passes) or none of it (nothing
            # does) needs no rank; placing it does, and so does the tile
            # slot k falls in
            need = (hi > lo) & (lo < k) & (placed | (hi > k))
            return jax.lax.cond(
                need, _ex_rank, lambda m: jnp.zeros(m.shape, jnp.int32), mask)

        p_lo, p_hi = start_ref[tile], start_ref[tile + 1]
        s_lo, s_hi = start_ref[tiles + tile], start_ref[tiles + tile + 1]
        p_rank = ranks(primary, p_lo, p_hi, jnp.logical_not(ties))
        s_rank = ranks(secondary, s_lo, s_hi, ties)
        keep_p = primary & (p_lo + p_rank < k)
        keep_s = secondary & (s_lo + s_rank < k)
        keep = keep_p | keep_s
        newu_ref[:] = jnp.where(keep, 0.0, u2)
        newv_ref[:] = jnp.where(keep, 0.0, v2)
        lo = start_ref[item]
        holds = (start_ref[item + 1] > lo) & (lo < k)
        holds_ref[0] = holds.astype(jnp.int32)

        @pl.when(holds)
        def _compact_the_class():
            # (a select between two masks is one Mosaic does not lower)
            kept = jnp.where(ties, keep_s.astype(jnp.int32),
                             keep_p.astype(jnp.int32)) != 0
            val, packed = _compact(kept, jnp.where(ties, s_rank, p_rank),
                                   v2, lo % _BLK)
            rows = pl.ds(out_rows, frame)
            cval[rows, :] = val
            cidx[rows, :] = jnp.where(
                packed != 0,
                tile * _TILE + (packed & ((1 << _src_bits(_TILE_ROWS)) - 1)),
                -1)

    @pl.when(live & (holds_ref[0] == 1))
    def _merge():
        # frame row r is output row first + r; before and after the frame
        # lie out_rows rows that hold no pair, so a block that the item
        # only walks through (the last item's, to the end) reads those
        first = start_ref[item] // _BLK * _BLK_ROWS
        shift = jnp.clip(blk * out_rows - first, -out_rows, frame)
        win = pl.ds(pl.multiple_of(out_rows + shift, _BLK_ROWS), out_rows)
        idx = cidx[win, :]
        vals_ref[:] = jnp.where(idx >= 0, cval[win, :], vals_ref[:])
        idx_ref[:] = jnp.where(idx >= 0, idx, idx_ref[:])


def _tile_rows(x, n, tiles):
    """A flat bucket as [rows, 128] float32: whole lanes, and whole
    (8, 128) tiles where it is one tile.  The last tile of several may
    hang over the end (Pallas masks what it writes there, the kernels
    mask what they read), so a bucket of whole lanes, which every large
    one is, goes in and comes out without a copy."""
    rows = -(-max(n, 1) // _LANES)
    if tiles == 1:
        rows = -(-rows // _BLK_ROWS) * _BLK_ROWS
    x = x.reshape(-1).astype(jnp.float32)
    if rows * _LANES != n:
        x = jnp.concatenate([x, jnp.zeros((rows * _LANES - n,), jnp.float32)])
    return x.reshape(rows, _LANES)


def _threshold_spec():
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    return pl.BlockSpec(memory_space=pltpu.SMEM)


@functools.partial(jax.jit, static_argnames=("interpret",))
def select_pack_counts(g: jax.Array, u: jax.Array, v: jax.Array,
                       threshold: jax.Array, interpret: bool = False):
    """The counting pass of a bucket of several tiles: int32 [tiles]
    counts of primaries (|v'| > threshold) and of ties (== threshold),
    what ``place_visits`` makes the schedule from."""
    import jax.experimental.pallas as pl

    n = g.shape[0]
    tiles = -(-n // _TILE)
    tile_spec = pl.BlockSpec((_TILE_ROWS, _LANES), lambda t: (t, 0))
    lanes = -(-tiles // _LANES) * _LANES
    counts = pl.pallas_call(
        functools.partial(_count_kernel, n),
        grid=(tiles,),
        in_specs=[tile_spec, tile_spec, tile_spec, _threshold_spec()],
        out_specs=pl.BlockSpec((_BLK_ROWS, lanes), lambda t: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((_BLK_ROWS, lanes), jnp.int32),
        name="bsc_select_pack_count",
        interpret=interpret,
    )(*(_tile_rows(x, n, tiles) for x in (g, u, v)),
      jnp.asarray(threshold, jnp.float32).reshape(1, 1))
    return counts[0, :tiles], counts[1, :tiles]


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def bsc_select_pack(g: jax.Array, u: jax.Array, v: jax.Array,
                    threshold: jax.Array, k: int, interpret: bool = False):
    """Fused momentum + sampled-boundary select + fixed-k pack + EF reset.

    Args: flat fp32 ``g``/``u``/``v`` of equal length ``n``; ``threshold``
    a traced scalar (the sampled magnitude boundary); static ``k``.
    Returns ``(vals[k], idx[k] int32 with -1 sentinels, new_u[n],
    new_v[n])`` — bit-identical to :func:`select_pack_ref`.
    """
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = g.shape[0]
    k = int(k)
    tiles, out_blocks, out_rows = select_pack_shape(n, k)
    operands = [_tile_rows(x, n, tiles) for x in (g, u, v)]
    operands.append(jnp.asarray(threshold, jnp.float32).reshape(1, 1))
    rows = operands[0].shape[0]
    dense = jax.ShapeDtypeStruct((rows, _LANES), jnp.float32)

    def pairs(krows):
        return (jax.ShapeDtypeStruct((krows, _LANES), jnp.float32),
                jax.ShapeDtypeStruct((krows, _LANES), jnp.int32))

    if tiles == 1:
        whole = pl.BlockSpec(memory_space=pltpu.VMEM)
        newu, newv, vals, idx = pl.pallas_call(
            functools.partial(_select_tile_kernel, k, n),
            in_specs=[whole, whole, whole, _threshold_spec()],
            out_specs=(whole,) * 4,
            # a class's run starts anywhere in the 8-row tile holding its
            # first slot and is at most the tile long
            out_shape=(dense, dense) + pairs(out_rows + rows + _BLK_ROWS),
            name="bsc_select_pack",
            interpret=interpret,
        )(*operands)
    else:
        item, blk, total, start = place_visits(
            *select_pack_counts(g, u, v, threshold, interpret=interpret),
            k, out_blocks, out_rows * _LANES)

        def of_tile(t, item, blk, total, start):
            return (jnp.where(item[t] >= tiles, item[t] - tiles, item[t]), 0)

        def of_block(t, item, blk, total, start):
            return (blk[t], 0)

        tile_spec = pl.BlockSpec((_TILE_ROWS, _LANES), of_tile)
        pair_spec = pl.BlockSpec((out_rows, _LANES), of_block)
        frame_rows = 2 * out_rows + _TILE_ROWS + _BLK_ROWS
        newu, newv, vals, idx = pl.pallas_call(
            functools.partial(_place_kernel, k, n, tiles),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4,
                grid=(item.shape[0],),
                in_specs=[tile_spec, tile_spec, tile_spec, _threshold_spec()],
                out_specs=(tile_spec, tile_spec, pair_spec, pair_spec),
                scratch_shapes=[pltpu.VMEM((frame_rows, _LANES), jnp.float32),
                                pltpu.VMEM((frame_rows, _LANES), jnp.int32),
                                pltpu.SMEM((1,), jnp.int32)],
            ),
            out_shape=(dense, dense) + pairs(out_blocks * out_rows),
            name="bsc_select_pack_place",
            interpret=interpret,
        )(item, blk, total, start, *operands)
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
