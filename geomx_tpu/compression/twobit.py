"""2-bit gradient quantization with error feedback.

Reference semantics (src/kvstore/gradient_compression.cc:118-189 +
gradient_compression-inl.h): residual += grad; elements whose residual
crosses ±threshold are transmitted as sign codes worth ±threshold, the rest
as 0; the transmitted amount is subtracted from the residual (error
feedback); 16 two-bit codes pack into one 32-bit word (16x compression,
GetCompressionFactor, gradient_compression.cc:102-109).

TPU-native: quantize/pack and unpack are one op each of ``ops/``
(fused Pallas kernels on a TPU, vectorized jnp elsewhere: ops/dispatch.py
decides); the packed int32 words are the wire payload, all-gathered
across the tier; each device unpacks all parties' codes and accumulates
±threshold contributions in fp32.
"""

from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from geomx_tpu.compression.base import Compressor
from geomx_tpu.ops import dispatch
from geomx_tpu.parallel.collectives import tier_scope


class TwoBitCompressor(Compressor):
    name = "2bit"

    def __init__(self, threshold: float = 0.5,
                 sparse_agg: "bool | None" = None):
        """The packed words' layout is the op's own (ops/twobit_pallas.py:
        row-blocked from the kernel, 16 consecutive codes a word from the
        jnp form); both are self-inverse and dequantize to identical
        values.

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
        if sparse_agg is None:
            from geomx_tpu.compression.sparseagg import sparse_agg_enabled
            sparse_agg = sparse_agg_enabled()
        self.sparse_agg = bool(sparse_agg)

    def init_leaf_state(self, leaf: jax.Array) -> Any:
        # error-feedback residual, same shape as the gradient
        return jnp.zeros(leaf.shape, jnp.float32)

    def quantize(self, g_flat: jax.Array, residual_flat: jax.Array):
        """Returns (packed int32 words, new residual)."""
        return dispatch.quantize_2bit(g_flat, residual_flat, self.threshold)

    def dequantize(self, words: jax.Array, n: int) -> jax.Array:
        return dispatch.dequantize_2bit(words, n, self.threshold)

    def allreduce_leaf(self, g: jax.Array, residual: Any, axis_name: str,
                       axis_size: int) -> Tuple[jax.Array, Any]:
        if self.sparse_agg and axis_size > 1:
            return self._allreduce_lattice(g, residual, axis_name,
                                           axis_size)
        shape, dtype, n = g.shape, g.dtype, g.size
        packed, new_res = self.quantize(g.reshape(-1), residual.reshape(-1))
        if axis_size == 1:
            out = self.dequantize(packed, n)
        else:
            with tier_scope(axis_name):
                gathered = lax.all_gather(packed, axis_name)  # [axis, words]
            # every party's values live on the same ±threshold grid
            parts = [self.dequantize(gathered[i], n)
                     for i in range(axis_size)]
            out = sum(parts[1:], parts[0])
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

    def wire_bytes_leaf(self, leaf: jax.Array) -> int:
        if self.sparse_agg:
            return leaf.size  # int8 sign codes on the lattice psum
        return 4 * dispatch.twobit_words(leaf.size)
