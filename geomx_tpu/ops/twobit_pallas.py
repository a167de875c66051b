"""Fused 2-bit quantization Pallas kernels, and their jnp form.

Semantics of the reference Quantize2BitImpl: codes 0/1/2 = {0,
+threshold, -threshold}, residual error feedback, 16 codes packed per
int32 word.  The jnp form below (``quantize_2bit_ref`` /
``dequantize_2bit_ref``, the only path off a TPU) packs 16 consecutive
elements a word; the kernels pack by the lane, as follows.  Both are
self-inverse and dequantize to identical values.

Layout: gradients are processed as [rows, 2048] fp32 blocks; within a
block, word (row, lane) packs the 16 elements {row*2048 + lane + 128*j}
(lane-strided, which is the VPU-friendly packing — no cross-lane
shuffles).  ``dequantize_2bit`` is the exact inverse; the packed words are
an opaque wire format.  The fusion saves three HBM round trips vs the
unfused XLA graph (residual read/write, code materialization, pack).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_LANES = 128
_PACK = 16
_BLOCK_COLS = _PACK * _LANES  # 2048 fp32 elements -> 128 packed int32
# Rows per grid step.  256 rows keeps the kernel's resident blocks
# (g, r, newr at 2 MB each + packed at 128 KB) ~6.3 MB, comfortably under
# the 16 MB scoped-vmem limit that a gridless call blows through at
# multi-million-element inputs (observed on v5e at 4M elements).
_BLOCK_ROWS = 256


def _kernel(thr, g_ref, r_ref, packed_ref, newr_ref):
    acc = g_ref[:] + r_ref[:]
    pos = acc >= thr
    neg = acc <= -thr
    codes = jnp.where(pos, 1, jnp.where(neg, 2, 0)).astype(jnp.int32)
    sent = jnp.where(pos, thr, jnp.where(neg, -thr, 0.0))
    newr_ref[:] = acc - sent
    # pack: word (row, lane) collects the 16 codes at columns lane + 128*j
    # (lane-strided).  Sixteen static [R, 128] column slices shifted and
    # summed elementwise — Mosaic has no middle-axis reduce_sum, so the
    # [R, 16, L] reshape+reduce formulation does not cross-lower.
    packed = codes[:, 0 * _LANES:1 * _LANES]
    for j in range(1, _PACK):
        packed = packed | (codes[:, j * _LANES:(j + 1) * _LANES] << (2 * j))
    packed_ref[:] = packed


def _dequant_kernel(thr, packed_ref, out_ref):
    # inverse of the lane-strided pack: sixteen static [R, 128] column
    # stores (no 3-D reshape/broadcast, which Mosaic cannot lower)
    words = packed_ref[:]
    for j in range(_PACK):
        codes = (words >> (2 * j)) & 3
        out_ref[:, j * _LANES:(j + 1) * _LANES] = jnp.where(
            codes == 1, thr, jnp.where(codes == 2, -thr, 0.0)
        ).astype(jnp.float32)


def _block_rows(rows: int) -> int:
    """Rows per grid step: capped at _BLOCK_ROWS for the vmem bound, but
    no larger than the tensor needs — a 1-row bias leaf must not be
    padded out to a 256-row block (rows is static under jit)."""
    return min(_BLOCK_ROWS, rows)


def _pad_to_block(x: jax.Array):
    """Pad flat x to [rows_padded, 2048] where rows_padded is a multiple of
    the grid's row block (so every grid step sees a full block); returns the
    true row count so callers can strip the padding from outputs."""
    n = x.shape[0]
    rows = max(1, -(-n // _BLOCK_COLS))
    br = _block_rows(rows)
    rows_padded = -(-rows // br) * br
    padded = rows_padded * _BLOCK_COLS
    if padded != n:
        x = jnp.concatenate([x, jnp.zeros((padded - n,), x.dtype)])
    return x.reshape(rows_padded, _BLOCK_COLS), n, rows


@functools.partial(jax.jit, static_argnames=("threshold", "interpret"))
def quantize_2bit(g: jax.Array, residual: jax.Array, threshold: float,
                  interpret: bool = False):
    """Returns (packed int32 [ceil(n/2048)*128], new residual [n])."""
    from jax.experimental import pallas as pl

    gf = g.reshape(-1).astype(jnp.float32)
    rf = residual.reshape(-1).astype(jnp.float32)
    g2, n, rows = _pad_to_block(gf)
    r2, _, _ = _pad_to_block(rf)
    rows_padded = g2.shape[0]
    br = _block_rows(rows)
    packed, newr = pl.pallas_call(
        functools.partial(_kernel, float(threshold)),
        grid=(rows_padded // br,),
        in_specs=[pl.BlockSpec((br, _BLOCK_COLS), lambda i: (i, 0)),
                  pl.BlockSpec((br, _BLOCK_COLS), lambda i: (i, 0))],
        out_specs=(pl.BlockSpec((br, _LANES), lambda i: (i, 0)),
                   pl.BlockSpec((br, _BLOCK_COLS), lambda i: (i, 0))),
        out_shape=(jax.ShapeDtypeStruct((rows_padded, _LANES), jnp.int32),
                   jax.ShapeDtypeStruct((rows_padded, _BLOCK_COLS),
                                        jnp.float32)),
        interpret=interpret,
    )(g2, r2)
    return packed[:rows].reshape(-1), newr.reshape(-1)[:n]


@functools.partial(jax.jit, static_argnames=("n", "threshold", "interpret"))
def dequantize_2bit(packed: jax.Array, n: int, threshold: float,
                    interpret: bool = False):
    from jax.experimental import pallas as pl

    rows = packed.shape[0] // _LANES
    br = _block_rows(rows)
    rows_padded = -(-rows // br) * br
    p2 = packed.reshape(rows, _LANES)
    if rows_padded != rows:
        p2 = jnp.concatenate(
            [p2, jnp.zeros((rows_padded - rows, _LANES), p2.dtype)])
    out = pl.pallas_call(
        functools.partial(_dequant_kernel, float(threshold)),
        grid=(rows_padded // br,),
        in_specs=[pl.BlockSpec((br, _LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, _BLOCK_COLS), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_padded, _BLOCK_COLS),
                                       jnp.float32),
        interpret=interpret,
    )(p2)
    return out.reshape(-1)[:n]


# -- the jnp form -----------------------------------------------------------

def pack2bit(codes: jax.Array) -> jax.Array:
    """Pack int codes in {0,1,2} ({zero, +thr, -thr}) into int32 words,
    16 consecutive codes a word."""
    pad = (-codes.shape[0]) % _PACK
    if pad:
        codes = jnp.concatenate([codes, jnp.zeros((pad,), codes.dtype)])
    codes = codes.reshape(-1, _PACK).astype(jnp.int32)
    shifts = jnp.arange(_PACK, dtype=jnp.int32) * 2
    return jnp.sum(codes << shifts[None, :], axis=1, dtype=jnp.int32)


def unpack2bit(words: jax.Array, n: int) -> jax.Array:
    """Inverse of pack2bit; returns int32 codes of length n."""
    shifts = jnp.arange(_PACK, dtype=jnp.int32) * 2
    codes = (words[:, None] >> shifts[None, :]) & 3
    return codes.reshape(-1)[:n]


def _codes_to_values(codes: jax.Array, threshold: float) -> jax.Array:
    return jnp.where(codes == 1, threshold,
                     jnp.where(codes == 2, -threshold, 0.0)).astype(jnp.float32)


def quantize_2bit_ref(g: jax.Array, residual: jax.Array, threshold: float):
    """Returns (packed int32 [ceil(n/16)], new residual [n])."""
    r = residual.reshape(-1).astype(jnp.float32) \
        + g.reshape(-1).astype(jnp.float32)
    codes = jnp.where(r >= threshold, 1,
                      jnp.where(r <= -threshold, 2, 0)).astype(jnp.int32)
    return pack2bit(codes), r - _codes_to_values(codes, threshold)


def dequantize_2bit_ref(words: jax.Array, n: int, threshold: float):
    return _codes_to_values(unpack2bit(words, n), threshold)


def words_ref(n: int) -> int:
    """Words :func:`quantize_2bit_ref` emits for ``n`` elements."""
    return -(-n // _PACK)


def words_kernel(n: int) -> int:
    """Words :func:`quantize_2bit` emits: 128 a 2048-element row, so a
    small leaf pads up to one row (the same n/16 asymptote)."""
    return _LANES * (-(-n // _BLOCK_COLS))
