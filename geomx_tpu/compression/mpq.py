"""Mixed-Precision Quantization (MPQ).

Reference semantics (README.md:24, examples/cnn_mpq.py:86-126): tensors
smaller than ``MXNET_KVSTORE_SIZE_LOWER_BOUND`` (default 200k elements,
kvstore_dist_server.h:183) are transmitted as fp16; larger tensors go
through Bi-Sparse sparsification.  The split is static per tensor, so it
maps cleanly onto XLA's static shapes: each pytree leaf is routed to one
sub-compressor at trace time.

Under the bucketed communication engine (compression/bucketing.py, the
dc-tier default) the "tensor" MPQ routes is a fused flat *bucket*: the
small-vs-large split happens at bucket granularity, so a bucket of many
small leaves crosses ``size_lower_bound`` as one tensor and takes the
sparse path its members would each have missed.
"""

from __future__ import annotations

from typing import Any, Tuple

import jax

from geomx_tpu.compression.base import Compressor
from geomx_tpu.compression.bisparse import BiSparseCompressor
from geomx_tpu.compression.fp16 import FP16Compressor


class MPQCompressor(Compressor):
    name = "mpq"

    def __init__(self, ratio: float = 0.01, size_lower_bound: int = 200_000,
                 bf16: bool = False):
        self.size_lower_bound = int(size_lower_bound)
        self.small = FP16Compressor(bf16=bf16)
        self.large = BiSparseCompressor(ratio=ratio)

    def _route(self, leaf: jax.Array) -> Compressor:
        return self.large if leaf.size >= self.size_lower_bound else self.small

    def init_leaf_state(self, leaf: jax.Array) -> Any:
        return self._route(leaf).init_leaf_state(leaf)

    def allreduce_leaf(self, g: jax.Array, state: Any, axis_name: str,
                       axis_size: int) -> Tuple[jax.Array, Any]:
        return self._route(g).allreduce_leaf(g, state, axis_name, axis_size)

    def wire_bytes_leaf(self, leaf: jax.Array) -> int:
        return self._route(leaf).wire_bytes_leaf(leaf)
