"""Fused bucket flatten/unflatten Pallas kernels.

``GradientBucketer.flatten``/``unflatten`` (compression/bucketing.py)
lower, per leaf, to one XLA concatenate operand / dynamic-slice copy.
The bucket layout is entirely static (leaf -> (bucket, offset, size)
resolves at trace time), so one Pallas kernel per bucket can place every
leaf with a straight-line program and collapse the op soup to a single
``tpu_custom_call`` per bucket and direction.

Layout on the chip.  A leaf starts at an arbitrary element offset of its
bucket, but TPU memory is tiled: a DMA (or a ref slice) must cover whole
``(8, 128)`` fp32 tiles, so a leaf cannot be copied to ``bucket[off:]``
as a column slice (the v5e compiler refuses ``[n, 1]`` views: "Slice
shape along dimension 1 must be aligned to tiling (128)").  Both kernels
therefore work on lane-dense ``[rows, 128]`` views, tile-aligned on both
sides, and move the data between the two alignments with the vector
unit: a row-major rotate of the loaded tiles (``pltpu.roll`` along lanes,
a one-row carry, then along sublanes) by ``off mod 1024`` elements.

- flatten: leaves are placed in offset order.  Each leaf's tiles are
  rotated right and stored over the bucket tiles they straddle; only the
  first tile of each store is a read-modify-write (it keeps what earlier
  leaves wrote below ``off``), and whatever a leaf writes past its own
  end is zero padding that the next leaf overwrites — or, for the last
  leaf, the bucket's zero tail.
- unflatten: each leaf's straddled bucket tiles are rotated left and the
  leading tiles stored to the (tile-padded) leaf.

Long leaves run as a ``fori_loop`` over fixed chunks so the Mosaic
program stays small; every ref is whole-array VMEM (a bucket plus its
leaves: ``~2x`` the bucket bytes), which bounds the bucket a kernel can
take — see ``MAX_FUSED_BUCKET_ELEMS``.  Single-leaf buckets need no
kernel at all (flatten is a pad, unflatten a slice) and never reach one.

Dtype handling stays OUTSIDE the kernels: callers pass 1-D fp32 views;
the tile padding of each leaf fuses into the ``reshape``/``astype`` that
produced the view.
"""

from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp

_LANES = 128
_TILE_ROWS = 8
_TILE = _TILE_ROWS * _LANES        # one fp32 tile: 1024 elements
_CHUNK_TILES = 64                  # tiles moved per loop step (256 KiB)

# A kernel holds the bucket and its leaves in VMEM.  48 MiB of the v5e's
# 128 MiB leaves room for the rotate temporaries; the default 4 MiB
# bucket (compression/bucketing.DEFAULT_BUCKET_BYTES) needs ~9 MiB.
_VMEM_CAP_BYTES = 48 * 1024 * 1024
_VMEM_SLACK_BYTES = 4 * 1024 * 1024
MAX_FUSED_BUCKET_ELEMS = (_VMEM_CAP_BYTES - _VMEM_SLACK_BYTES) // 8


def flat_roll(x, shift: int):
    """Rotate ``x`` [R, 128] right by ``shift`` elements in row-major
    (flat index) order: ``out.flat[f] = x.flat[(f - shift) mod R*128]``."""
    from jax.experimental.pallas import tpu as pltpu

    rows, lanes = divmod(shift, _LANES)
    if lanes:
        y = pltpu.roll(x, lanes, axis=1)
        lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        # lanes that wrapped belong to the row above
        x = jnp.where(lane >= lanes, y, pltpu.roll(y, 1, axis=0))
    if rows:
        x = pltpu.roll(x, rows, axis=0)
    return x


def _for_each_chunk(ntiles: int, chunk):
    """``chunk(first_tile, n)`` over ``ntiles`` tiles: full chunks in a
    ``fori_loop`` (traced ``first_tile``), the remainder statically."""
    full = ntiles // _CHUNK_TILES
    if full:
        def body(c, carry):
            chunk(c * _CHUNK_TILES, _CHUNK_TILES)
            return carry
        jax.lax.fori_loop(0, full, body, 0)
    rest = ntiles - full * _CHUNK_TILES
    if rest:
        chunk(full * _CHUNK_TILES, rest)


def _rows(tile, n: int):
    """Ref row slice covering ``n`` tiles from tile index ``tile``."""
    import jax.experimental.pallas as pl
    start = tile * _TILE_ROWS
    if not isinstance(start, int):
        start = pl.multiple_of(start, _TILE_ROWS)
    return pl.ds(start, n * _TILE_ROWS)


def _flatten_kernel(offsets, fill, *refs):
    """refs = [*leaf_refs (tile-padded, zero tails), bucket_out_ref]."""
    leaf_refs, out_ref = refs[:-1], refs[-1]
    zero_from = fill // _TILE * _TILE_ROWS
    out_ref[zero_from:, :] = jnp.zeros(
        (out_ref.shape[0] - zero_from, _LANES), jnp.float32)
    head_flat = (
        jax.lax.broadcasted_iota(jnp.int32, (_TILE_ROWS, _LANES), 0) * _LANES
        + jax.lax.broadcasted_iota(jnp.int32, (_TILE_ROWS, _LANES), 1))

    for leaf_ref, off in zip(leaf_refs, offsets):
        tile0, shift = divmod(off, _TILE)

        def chunk(t, n, leaf_ref=leaf_ref, tile0=tile0, shift=shift):
            x = leaf_ref[_rows(t, n), :]
            if not shift:
                out_ref[_rows(tile0 + t, n), :] = x
                return
            x = flat_roll(jnp.concatenate(
                [x, jnp.zeros((_TILE_ROWS, _LANES), jnp.float32)]), shift)
            head = _rows(tile0 + t, 1)
            out_ref[head, :] = jnp.where(head_flat >= shift,
                                         x[:_TILE_ROWS], out_ref[head, :])
            out_ref[_rows(tile0 + t + 1, n), :] = x[_TILE_ROWS:]

        _for_each_chunk(leaf_ref.shape[0] // _TILE_ROWS, chunk)


def _unflatten_kernel(offsets, bucket_ref, *leaf_refs):
    """leaf_refs are tile-padded outputs; the pad carries bucket bytes
    past the leaf's end, which the caller slices away."""
    for leaf_ref, off in zip(leaf_refs, offsets):
        tile0, shift = divmod(off, _TILE)

        def chunk(t, n, leaf_ref=leaf_ref, tile0=tile0, shift=shift):
            if not shift:
                leaf_ref[_rows(t, n), :] = bucket_ref[_rows(tile0 + t, n), :]
                return
            x = bucket_ref[_rows(tile0 + t, n + 1), :]
            x = flat_roll(x, (n + 1) * _TILE - shift)
            leaf_ref[_rows(t, n), :] = x[:n * _TILE_ROWS]

        _for_each_chunk(leaf_ref.shape[0] // _TILE_ROWS, chunk)


def _tiles(n: int) -> int:
    return -(-n // _TILE)


def _bucket_members(layout, b: int):
    """(leaf index, offset, size) of bucket ``b``'s non-empty leaves in
    offset order; they must tile the bucket's fill without gaps (the
    flatten kernel relies on each leaf starting where the last ended)."""
    members = sorted((off, i, size) for i, (bk, off, size)
                     in enumerate(layout) if bk == b and size)
    end = 0
    for off, _i, size in members:
        if off != end:
            raise ValueError(
                f"bucket {b} layout is not contiguous at offset {off} "
                f"(previous leaf ends at {end})")
        end = off + size
    return [(i, off, size) for off, i, size in members], end


def _compiler_params(total: int, leaf_sizes: Sequence[int]):
    """Raise the scoped-VMEM limit to what the whole-array refs need."""
    from jax.experimental.pallas import tpu as pltpu

    need = 4 * _TILE * (_tiles(total) + 1 + sum(map(_tiles, leaf_sizes)))
    need += _VMEM_SLACK_BYTES
    if need > _VMEM_CAP_BYTES:
        raise ValueError(
            f"a {total}-element multi-leaf bucket needs {need} bytes of "
            f"VMEM; the fused bucket kernels take buckets up to "
            f"{MAX_FUSED_BUCKET_ELEMS} elements — lower GEOMX_BUCKET_BYTES")
    return pltpu.CompilerParams(vmem_limit_bytes=need)


@functools.partial(jax.jit, static_argnames=("layout", "bucket_sizes",
                                             "interpret"))
def fused_flatten(leaves: Sequence[jax.Array],
                  layout: Tuple[Tuple[int, int, int], ...],
                  bucket_sizes: Tuple[int, ...],
                  interpret: bool = False) -> List[jax.Array]:
    """Gather 1-D fp32 ``leaves`` into flat fp32 buckets, one kernel per
    multi-leaf bucket.

    ``layout[i] = (bucket, offset, size)`` for leaf i; ``bucket_sizes``
    are the padded bucket lengths.  Tail padding is zero-filled, matching
    ``GradientBucketer.flatten`` exactly (a pure permutation, so the
    result is bit-identical to the jnp concatenate path).
    """
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    buckets = []
    for b, total in enumerate(bucket_sizes):
        members, fill = _bucket_members(layout, b)
        if len(members) <= 1:
            flat = (leaves[members[0][0]] if members
                    else jnp.zeros((0,), jnp.float32))
            buckets.append(jnp.pad(flat, (0, total - fill)))
            continue
        sizes = [size for _i, _off, size in members]
        out = pl.pallas_call(
            functools.partial(_flatten_kernel,
                              tuple(off for _i, off, _s in members), fill),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * len(members),
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct(
                ((_tiles(total) + 1) * _TILE_ROWS, _LANES), jnp.float32),
            compiler_params=_compiler_params(total, sizes),
            interpret=interpret,
        )(*[jnp.pad(leaves[i], (0, -size % _TILE)).reshape(-1, _LANES)
            for i, _off, size in members])
        buckets.append(out.reshape(-1)[:total])
    return buckets


@functools.partial(jax.jit, static_argnames=("layout", "leaf_sizes",
                                             "interpret"))
def fused_unflatten(buckets: Sequence[jax.Array],
                    layout: Tuple[Tuple[int, int, int], ...],
                    leaf_sizes: Tuple[int, ...],
                    interpret: bool = False) -> List[jax.Array]:
    """Scatter flat fp32 buckets back into 1-D fp32 leaves, one kernel
    per multi-leaf bucket (the caller reshapes/casts to the original leaf
    shapes/dtypes)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    leaves = [jnp.zeros((0,), jnp.float32)] * len(leaf_sizes)
    for b, bucket in enumerate(buckets):
        members, _fill = _bucket_members(layout, b)
        if len(members) <= 1:
            for i, off, size in members:
                leaves[i] = bucket[off:off + size]
            continue
        total = bucket.shape[0]
        sizes = [size for _i, _off, size in members]
        rows = (_tiles(total) + 1) * _TILE_ROWS
        out = pl.pallas_call(
            functools.partial(_unflatten_kernel,
                              tuple(off for _i, off, _s in members)),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
            out_specs=tuple(pl.BlockSpec(memory_space=pltpu.VMEM)
                            for _ in members),
            out_shape=tuple(jax.ShapeDtypeStruct(
                (_tiles(size) * _TILE_ROWS, _LANES), jnp.float32)
                for size in sizes),
            compiler_params=_compiler_params(total, sizes),
            interpret=interpret,
        )(jnp.pad(bucket, (0, rows * _LANES - total)).reshape(rows, _LANES))
        for (i, _off, size), leaf in zip(members, out):
            leaves[i] = leaf.reshape(-1)[:size]
    return leaves


def flatten_ref(leaves: Sequence[jax.Array],
                layout: Tuple[Tuple[int, int, int], ...],
                bucket_sizes: Tuple[int, ...]) -> List[jax.Array]:
    """jnp form of :func:`fused_flatten` (one XLA concatenate operand per
    leaf), bit-identical: the only path off a TPU and the kernel's
    oracle."""
    buckets = []
    for b, total in enumerate(bucket_sizes):
        members, fill = _bucket_members(layout, b)
        pieces = [leaves[i] for i, _off, _size in members]
        if total - fill or not pieces:
            pieces.append(jnp.zeros((total - fill,), jnp.float32))
        buckets.append(pieces[0] if len(pieces) == 1
                       else jnp.concatenate(pieces))
    return buckets


def unflatten_ref(buckets: Sequence[jax.Array],
                  layout: Tuple[Tuple[int, int, int], ...],
                  leaf_sizes: Tuple[int, ...]) -> List[jax.Array]:
    """jnp form of :func:`fused_unflatten` (one slice per leaf)."""
    return [buckets[b][off:off + size] for b, off, size in layout]
