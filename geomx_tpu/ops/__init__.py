"""Custom TPU kernels (Pallas) for the hot ops.

The reference implements its custom math as CPU loops + CUDA kernels
(gradient_compression-inl.h, gradient_compression.cu); here the
numerically custom pieces are Pallas TPU kernels, fused so a gradient
makes one HBM round trip:

- ``quantize_2bit``: residual += grad; threshold compare; pack 16 2-bit
  codes per int32 word; residual -= sent — one pass.
- ``dequantize_2bit``: unpack + scale.
- ``bsc_select_pack`` / ``bsc_scatter_add``: the Bi-Sparse dc-tier hot
  path — momentum correction, sampled-boundary select, fixed-k
  (value, index) pack and error-feedback reset fused (a counting and a
  placing pass over the bucket, one call where it is one tile),
  plus the dense scatter-add reconstruction (docs/kernels.md).
- ``fused_flatten`` / ``fused_unflatten``: the bucket (un)flatten as a
  single DMA kernel instead of one XLA copy per pytree leaf.
- ``flash_attention`` / ``fused_attention``: online-softmax attention
  for the long-context path — the [L, L] score matrix never reaches
  HBM (the reference has no attention operator at all).
- ``fused_apply`` (optim_pallas): SGD-momentum/Adam applied over the
  flat fp32 buckets in one VMEM-resident pass per bucket, replacing
  the per-leaf optax chain on the hot path (``GEOMX_FUSED_OPTIM``).

Three ops of a decoder's layers are imported from their modules:
``dispatch.kda`` (the chunkwise gated delta rule with a per-channel
decay: the kernel pair ``kda_pallas.kda_scan`` with the chunk-to-chunk
state in VMEM, forward and backward, or its jnp form ``kda.kda_chunked``),
``dispatch.ssd`` (the Mamba-2 state-space scan in its chunkwise matrix
form, ``ssd.ssd_chunked``: plain matrix products, no kernel yet)
and ``held_experts.held_experts`` (the routed experts
one chip holds: the sorted assignments walked a pool at a time in a loop
of as many trips as pools exist, the experts (SwiGLU, or un-gated
squared ReLU) as JAX's megablox grouped products).

The names exported here are the kernels themselves (native on a TPU,
``interpret=True`` for the CPU parity tests).  The compression engine does
not call them: it calls ``ops.dispatch``, the one place that decides
between a kernel and its jnp form, from the platform.
"""

from geomx_tpu.ops.bsc_pallas import bsc_scatter_add, bsc_select_pack
from geomx_tpu.ops.bucket_pallas import fused_flatten, fused_unflatten
from geomx_tpu.ops.flash_attention import (flash_attention,
                                           flash_attention_bwd,
                                           flash_attention_with_lse,
                                           fused_attention,
                                           fused_attention_supported)
from geomx_tpu.ops.optim_pallas import (FusedOptimSpec, FusedOptimizer,
                                        fused_adam, fused_apply,
                                        fused_optim_enabled,
                                        fused_optimizer,
                                        fused_sgd_momentum, fused_spec_of,
                                        unfused_apply)
from geomx_tpu.ops.twobit_pallas import dequantize_2bit, quantize_2bit

__all__ = ["quantize_2bit", "dequantize_2bit",
           "bsc_select_pack", "bsc_scatter_add",
           "fused_flatten", "fused_unflatten",
           "flash_attention", "flash_attention_bwd",
           "flash_attention_with_lse", "fused_attention",
           "fused_attention_supported",
           "FusedOptimSpec", "FusedOptimizer", "fused_optimizer",
           "fused_spec_of", "fused_optim_enabled", "fused_apply",
           "unfused_apply", "fused_sgd_momentum", "fused_adam"]
