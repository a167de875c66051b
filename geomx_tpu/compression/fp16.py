"""FP16 low-precision transmission.

Reference behavior: compute fp32, transmit fp16, server keeps an fp32
"multi-precision" master copy and accumulates in fp32
(README.md:23; server store src/kvstore/kvstore_dist_server.h:348-381).

TPU-native: cast the per-party gradient to 16-bit, all-gather the 16-bit
payload across the tier (halving wire bytes — the only thing the reference
optimization buys), then upcast and reduce in fp32 locally.  ``bf16=True``
swaps IEEE fp16 for bfloat16, which is the TPU-native 16-bit type (same
wire size, far better dynamic range for gradients).
"""

from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from geomx_tpu.compression.base import Compressor
from geomx_tpu.parallel.collectives import tier_scope


class FP16Compressor(Compressor):
    name = "fp16"

    def __init__(self, bf16: bool = False,
                 sparse_agg: "bool | None" = None):
        """``sparse_agg`` (default ``GEOMX_SPARSE_AGG``): sum in the
        quantized lattice per THC (compression/sparseagg.py) — one
        shared scale negotiated across the axis (scalar pmax), int16
        codes with party-count headroom summed EXACTLY by the
        collective, one dequantize.  Same 2-byte wire; the [axis, n]
        gathered-then-upcast per-party intermediate disappears."""
        self.wire_dtype = jnp.bfloat16 if bf16 else jnp.float16
        if sparse_agg is None:
            from geomx_tpu.compression.sparseagg import sparse_agg_enabled
            sparse_agg = sparse_agg_enabled()
        self.sparse_agg = bool(sparse_agg)

    def allreduce_leaf(self, g: jax.Array, state: Any, axis_name: str,
                       axis_size: int) -> Tuple[jax.Array, Any]:
        wire = g.astype(self.wire_dtype)
        if axis_size == 1:
            return wire.astype(g.dtype), state
        if self.sparse_agg:
            from geomx_tpu.compression.sparseagg import \
                lattice_allreduce_fp16
            flat = lattice_allreduce_fp16(g.reshape(-1), axis_name,
                                          axis_size)
            return flat.reshape(g.shape).astype(g.dtype), state
        with tier_scope(axis_name):
            gathered = lax.all_gather(wire, axis_name)  # [axis, *shape] 16-bit
        total = jnp.sum(gathered.astype(g.dtype), axis=0)  # fp32 accumulate
        return total, state

    def wire_bytes_leaf(self, leaf: jax.Array) -> int:
        return leaf.size * 2
