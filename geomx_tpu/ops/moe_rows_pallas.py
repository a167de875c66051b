"""An expert pool's rows back into the token array, at a cost that follows
the rows that arrived.

``ops/held_experts.py`` walks the sorted assignments a pool of places at a
time: the pool's tokens are gathered, run through the grouped products
and scatter-added into the layer's output.  XLA's row scatter-add on a
v5e pays by the *index*: 3.0 ms for 32,768 places of 2,048 float32 in the
Trinity cell's step (93 ns a place, whether the place holds a row or not,
`unique_indices` or not: PERF.md section 5), so a pool sized for skewed
routing costs its places, not its rows.  :func:`moe_row_scatter_add`
(``y[token] += out`` for the pool's valid places, ``y`` updated in place)
pays by the row that arrived.

The way in needs no kernel: XLA's row *gather* runs at what its bytes
cost inside a program (0.2 ms for the same 32,768 places, 6 ns a place;
PERF.md), and a gather kernel built like the scatter-add below (one copy
a valid row into float32 slabs) lost to it by the relayout pass its
operand needs (PR 34: 1.18 against 0.64 ms in the tool, 10.3 against 2.5
ms a step in the cell) and was deleted.

**Rows as slabs.**  A TPU array ``[T, d]`` is tiled ``(8, 128)``: a row
is a stride through eight-row tiles, and Mosaic refuses a slice or a DMA
of one row of it ("Slice shape along dimension 0 must be aligned to
tiling (8), but is 1").  The kernel therefore sees ``y`` as float32 slabs
``[T, d / 128, 128]`` (`_slabs`): a token is an index of the leading,
untiled dimension and its row one contiguous piece of HBM, which one DMA
moves (``d`` is padded to whole lanes, which the cells' 2,048 and 2,304
are).  XLA makes that view in a relayout pass (nothing, for the zeros a
walk starts from) and turns the result back into ``[T, d]`` in another;
the kernel turns a tile's ``[tile, d]`` block of ``out`` into slabs with
strided loads and stores in VMEM (``buf[:, c, :]``: sublane ``c`` of
every slab).

**A grid step** takes a tile of places (`tile_rows`: as many as fit the
VMEM budget, a power of two up to 512).  The token ids arrive as scalar
prefetch; the scalar core starts one HBM <-> VMEM copy a valid place, all
of a segment's in flight on one shared semaphore, then waits as many
times.  The places of no assignment are a suffix of the pool, so a tile
is full, holds the boundary or is empty: an empty tile starts no copy,
and its ``out`` block's index map stays on the last tile that holds a
row, so the pipeline fetches nothing either.

**Duplicates.**  A token is unique inside one expert's run, but a tile
can straddle runs and a token that chose several held experts then sits
more than once in it; two read-add-write chains of one row in flight
together would lose an addend.  The kernel splits a tile at the run
boundaries (the runs' ends arrive as scalar prefetch): one *segment* (a
run's part of a tile) at a time reads its rows of ``y``, waits, adds,
writes them back and waits, so every copy in flight together touches a
different row, and a later segment or tile reads what the earlier one
wrote (grid steps are sequential).  There are at most tiles + runs
segments.  The addends of one row are therefore summed in the order of
the experts' ids, as XLA's scatter-add sums them in the order of the
places: equal wherever the order cannot matter (up to two held
assignments a token: 0 + a + b) and within the reordering of a float32
sum of at most ``k`` terms elsewhere.

Interpret mode runs the same kernel with copies that complete at their
start: it holds the arithmetic and the segment walk, not the overlap.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# what a grid step's blocks and scratch may take of VMEM, and the most
# places a tile holds
VMEM_BUDGET = 12 * 1024 * 1024
MAX_TILE = 512
# copies started (or waited for) between two tests of the loop's bound
UNROLL = 8


def row_scatter_add_ref(y, out, token, sizes):
    """The jnp form: places of no assignment point outside ``y``."""
    del sizes
    return y.at[token].add(out, mode="drop")


def slabs_are_whole(d: int) -> bool:
    """Whether a row of `d` floats is whole `(8, 128)` tiles as a slab
    (`d` a multiple of 1,024).  Elsewhere the slabs' sublanes pad (18 to 24
    at 2,304) and XLA relayouts the result in two passes through a
    transposed copy: in `kimilinear-fsa-1c` that cost 1.19 GB of peak
    memory (+10%) for 4.5 ms of a 797 ms step (PERF.md, PR 34), so the
    door (`ops.dispatch.row_scatter_add`) keeps XLA's scatter-add there.
    The kernel itself takes any width (the tests and the timing tool call
    it by name)."""
    return d % (8 * LANES) == 0


def tile_rows(places: int, d: int, max_tile: int = MAX_TILE) -> int:
    """Places a grid step takes: the largest power of two up to `max_tile`
    that divides `places` and whose scratch (float32 slabs, their second
    dimension padded to whole tiles of 8) and two pipelined float32
    `[tile, d]` blocks of `out` fit `VMEM_BUDGET`."""
    chunks = -(-d // LANES)
    a_row = 4 * LANES * (-(-chunks // 8) * 8 + 2 * chunks)
    tile = max_tile
    while tile > 8 and (places % tile or tile * a_row > VMEM_BUDGET):
        tile //= 2
    if places % tile:
        raise ValueError(f"{places} places are not whole tiles of {tile}")
    return tile


def _slabs(y):
    """float32 [T, d] -> [T, ceil(d / 128), 128]: a row as one slab."""
    pad = (-y.shape[1]) % LANES
    if pad:
        y = jnp.pad(y, ((0, 0), (0, pad)))
    return y.reshape(y.shape[0], -1, LANES)


def _each(lo, hi, body):
    """`body(r)` for r in [lo, hi), `UNROLL` a trip while that many are
    left: the scalar core issues a copy in a few instructions, and the
    loop's own test and branch would be as many again."""
    groups = (hi - lo) // UNROLL

    def group(g, carry):
        for u in range(UNROLL):
            body(lo + g * UNROLL + u)
        return carry

    def one(r, carry):
        body(r)
        return carry

    lax.fori_loop(0, groups, group, 0)
    lax.fori_loop(lo + groups * UNROLL, hi, one, 0)


def _scatter_kernel(token, ends, y_in, out, y_hbm, buf, sem_in, sem_out, *,
                    tile, chunks, runs):
    del y_in    # y_hbm's own buffer (input_output_aliases)
    base = pl.program_id(0) * tile
    stop = jnp.minimum(ends[runs - 1], base + tile)

    def segment(lo):
        # to the end of the run that holds place `lo`, or the tile's
        hi = lax.fori_loop(
            0, runs, lambda e, hi: jnp.where(ends[e] > lo,
                                             jnp.minimum(hi, ends[e]), hi),
            stop)
        rows = lambda r: (y_hbm.at[token[r]], buf.at[r - base])
        _each(lo, hi, lambda r: pltpu.make_async_copy(
            *rows(r), sem_in).start())
        _each(lo, hi, lambda r: pltpu.make_async_copy(
            y_hbm.at[0], buf.at[0], sem_in).wait())
        # the whole tile's add: the rows of other segments hold what was
        # written back already or what their own read will replace
        for c in range(chunks):
            buf[:, c, :] = buf[:, c, :] + out[:, c * LANES:(c + 1) * LANES]
        _each(lo, hi, lambda r: pltpu.make_async_copy(
            *rows(r)[::-1], sem_out).start())
        _each(lo, hi, lambda r: pltpu.make_async_copy(
            buf.at[0], y_hbm.at[0], sem_out).wait())
        return hi

    lax.while_loop(lambda lo: lo < stop, segment, base)


@functools.partial(jax.jit, static_argnames=("interpret", "max_tile"))
def moe_row_scatter_add(y, out, token, sizes, *, interpret: bool = False,
                        max_tile: int = MAX_TILE):
    """y [T, d] float32, out [places, d] float32, token [places] int32,
    sizes [E] int32 (the places of each expert's run, runs in order from
    place 0 on; the places behind them hold no assignment) -> y with
    ``out``'s valid rows added to ``y[token]``, in y's own buffer.  A
    token may sit in several runs, once in each (the module docstring
    says how the kernel keeps their updates apart and what that makes of
    the order of a row's addends)."""
    (t, d), places = y.shape, token.shape[0]
    slabs = _slabs(y)
    chunks = slabs.shape[1]
    if chunks * LANES != d:
        out = jnp.pad(out, ((0, 0), (0, chunks * LANES - d)))
    tile = tile_rows(places, d, max_tile)
    ends = jnp.cumsum(sizes.astype(jnp.int32))
    # an empty tile keeps the block of the last tile that holds a row
    block = lambda i, token, ends: (
        jnp.minimum(i, jnp.maximum(ends[sizes.shape[0] - 1] - 1, 0) // tile),
        0)
    slabs = pl.pallas_call(
        functools.partial(_scatter_kernel, tile=tile, chunks=chunks,
                          runs=sizes.shape[0]),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(places // tile,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec((tile, chunks * LANES), block)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((tile, chunks, LANES), jnp.float32),
                            pltpu.SemaphoreType.DMA(()),
                            pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct(slabs.shape, jnp.float32),
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="moe_row_scatter_add", interpret=interpret,
    )(token.astype(jnp.int32), ends, slabs, out)
    return slabs.reshape(t, -1)[:, :d]
