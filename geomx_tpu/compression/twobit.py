"""2-bit gradient quantization with error feedback.

Reference semantics (src/kvstore/gradient_compression.cc:118-189 +
gradient_compression-inl.h): residual += grad; elements whose residual
crosses ±threshold are transmitted as sign codes worth ±threshold, the rest
as 0; the transmitted amount is subtracted from the residual (error
feedback); 16 two-bit codes pack into one 32-bit word (16x compression,
GetCompressionFactor, gradient_compression.cc:102-109).

TPU-native: the quantize/pack is vectorized jnp (a Pallas kernel drops in
via ``geomx_tpu.ops``); the packed int32 words are the wire payload,
all-gathered across the tier; each device unpacks all parties' codes and
accumulates ±threshold contributions in fp32.
"""

from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from geomx_tpu.compression.base import Compressor
from geomx_tpu.parallel.collectives import tier_scope

_CODES_PER_WORD = 16  # 2 bits per element, int32 words


def _pad_len(n: int) -> int:
    return (-n) % _CODES_PER_WORD


def pack2bit(codes: jax.Array) -> jax.Array:
    """Pack int codes in {0,1,2} ({zero, +thr, -thr}) into int32 words."""
    n = codes.shape[0]
    pad = _pad_len(n)
    if pad:
        codes = jnp.concatenate([codes, jnp.zeros((pad,), codes.dtype)])
    codes = codes.reshape(-1, _CODES_PER_WORD).astype(jnp.int32)
    shifts = jnp.arange(_CODES_PER_WORD, dtype=jnp.int32) * 2
    return jnp.sum(codes << shifts[None, :], axis=1, dtype=jnp.int32)


def unpack2bit(words: jax.Array, n: int) -> jax.Array:
    """Inverse of pack2bit; returns int32 codes of length n."""
    shifts = jnp.arange(_CODES_PER_WORD, dtype=jnp.int32) * 2
    codes = (words[:, None] >> shifts[None, :]) & 3
    return codes.reshape(-1)[:n]


def _codes_to_values(codes: jax.Array, threshold: float) -> jax.Array:
    # 0 -> 0, 1 -> +threshold, 2 -> -threshold
    return jnp.where(codes == 1, threshold,
                     jnp.where(codes == 2, -threshold, 0.0)).astype(jnp.float32)


class TwoBitCompressor(Compressor):
    name = "2bit"

    def __init__(self, threshold: float = 0.5,
                 use_pallas: "bool | None" = None,
                 pallas_interpret: bool = False,
                 sparse_agg: "bool | None" = None):
        """``use_pallas`` switches quantize/dequantize to the fused Pallas
        kernels in geomx_tpu.ops (one HBM pass; TPU-native path).  The wire
        format differs between the paths but both are self-inverse, and the
        dequantized values are identical.  Default: Pallas on TPU (the
        fused kernel measures ~15x faster than the unfused jnp graph at
        4M elements — BENCH_r04 microbench), jnp elsewhere (Pallas
        interpret mode is far slower than XLA:CPU).  GEOMX_TWOBIT_PALLAS=0
        opts out.

        ``sparse_agg`` (default ``GEOMX_SPARSE_AGG``): sum in the
        quantized lattice per THC (compression/sparseagg.py) — the
        static ±threshold grid IS the shared scale, so the per-party
        ±1 sign codes psum EXACTLY as int8 and one scale lands fp32.
        Wire: n int8 bytes instead of the packed n/4 (4x the packed
        payload, but the merge is one integer collective with no
        [axis, n] per-party unpack intermediates — the THC trade)."""
        if threshold <= 0:
            raise ValueError("threshold must be greater than 0")  # gc.cc:50
        self.threshold = float(threshold)
        if use_pallas is None:
            from geomx_tpu.compression.base import default_on_tpu
            use_pallas = default_on_tpu("GEOMX_TWOBIT_PALLAS")
        self.use_pallas = use_pallas
        self.pallas_interpret = pallas_interpret
        if sparse_agg is None:
            from geomx_tpu.compression.sparseagg import sparse_agg_enabled
            sparse_agg = sparse_agg_enabled()
        self.sparse_agg = bool(sparse_agg)

    def init_leaf_state(self, leaf: jax.Array) -> Any:
        # error-feedback residual, same shape as the gradient
        return jnp.zeros(leaf.shape, jnp.float32)

    def quantize(self, g_flat: jax.Array, residual_flat: jax.Array):
        """Returns (packed int32 words, new residual)."""
        r = residual_flat + g_flat
        codes = jnp.where(r >= self.threshold, 1,
                          jnp.where(r <= -self.threshold, 2, 0)).astype(jnp.int32)
        sent = _codes_to_values(codes, self.threshold)
        new_residual = r - sent
        return pack2bit(codes), new_residual

    def dequantize(self, words: jax.Array, n: int) -> jax.Array:
        return _codes_to_values(unpack2bit(words, n), self.threshold)

    def allreduce_leaf(self, g: jax.Array, residual: Any, axis_name: str,
                       axis_size: int) -> Tuple[jax.Array, Any]:
        if self.sparse_agg and axis_size > 1:
            return self._allreduce_lattice(g, residual, axis_name,
                                           axis_size)
        if self.use_pallas:
            return self._allreduce_pallas(g, residual, axis_name, axis_size)
        shape, dtype = g.shape, g.dtype
        gf = g.reshape(-1).astype(jnp.float32)
        words, new_res = self.quantize(gf, residual.reshape(-1))
        if axis_size == 1:
            out = self.dequantize(words, gf.shape[0])
        else:
            with tier_scope(axis_name):
                gathered = lax.all_gather(words, axis_name)  # [axis, words] int32
            # sum of per-party signs, then scale once — exact since every
            # party's dequantized values live on the same ±threshold grid
            codes = (gathered[:, :, None] >>
                     (jnp.arange(_CODES_PER_WORD, dtype=jnp.int32) * 2)[None, None, :]) & 3
            signs = jnp.where(codes == 1, 1, jnp.where(codes == 2, -1, 0))
            total_signs = jnp.sum(signs, axis=0).reshape(-1)[:gf.shape[0]]
            out = total_signs.astype(jnp.float32) * self.threshold
        return out.reshape(shape).astype(dtype), new_res.reshape(shape)

    def _allreduce_lattice(self, g: jax.Array, residual: Any,
                           axis_name: str, axis_size: int
                           ) -> Tuple[jax.Array, Any]:
        """Homomorphic 2-bit merge: quantize with the same error
        feedback, then psum the ±1 sign codes on the int8 lattice and
        scale once — no packed gather, no per-party unpack
        (compression/sparseagg.py)."""
        from geomx_tpu.compression.sparseagg import lattice_allreduce_signs

        shape, dtype = g.shape, g.dtype
        gf = g.reshape(-1).astype(jnp.float32)
        r = residual.reshape(-1) + gf
        codes = jnp.where(r >= self.threshold, 1,
                          jnp.where(r <= -self.threshold, -1, 0)
                          ).astype(jnp.int8)
        new_res = r - codes.astype(jnp.float32) * self.threshold
        out = lattice_allreduce_signs(codes, self.threshold, axis_name,
                                      axis_size)
        return out.reshape(shape).astype(dtype), new_res.reshape(shape)

    def _allreduce_pallas(self, g: jax.Array, residual: Any, axis_name: str,
                          axis_size: int) -> Tuple[jax.Array, Any]:
        from geomx_tpu.ops import dequantize_2bit, quantize_2bit

        shape, dtype, n = g.shape, g.dtype, g.size
        interp = self.pallas_interpret
        packed, new_res = quantize_2bit(g.reshape(-1), residual.reshape(-1),
                                        self.threshold, interpret=interp)
        if axis_size == 1:
            out = dequantize_2bit(packed, n, self.threshold, interpret=interp)
        else:
            with tier_scope(axis_name):
                gathered = lax.all_gather(packed, axis_name)  # [axis, words]
            parts = [dequantize_2bit(gathered[i], n, self.threshold,
                                     interpret=interp)
                     for i in range(axis_size)]
            out = sum(parts[1:], parts[0])
        return out.reshape(shape).astype(dtype), new_res.reshape(shape)

    def wire_bytes_leaf(self, leaf: jax.Array) -> int:
        n = leaf.size
        if self.sparse_agg:
            return n  # int8 sign codes on the lattice psum
        if self.use_pallas:
            # the Pallas wire format is row-blocked: 128 int32 words per
            # 2048-element row (geomx_tpu/ops/twobit_pallas.py), so small
            # leaves pad up to one row — same n/4 asymptote, honest
            # accounting for the padding
            from geomx_tpu.ops.twobit_pallas import _BLOCK_COLS, _LANES
            return 4 * _LANES * (-(-n // _BLOCK_COLS))
        return 4 * ((n + _CODES_PER_WORD - 1) // _CODES_PER_WORD)
