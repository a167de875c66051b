"""A causal decoder of GLM-4-MoE-Lite blocks (`model_type`
``glm4_moe_lite``; the key set is DeepSeek-V3's): pre-norm blocks of latent
attention and a SwiGLU MLP (the leading dense layers) or an expert layer
with one shared expert, and a multi-token-prediction module behind the
last block,

    h += Attn(N1(h));  h += FFN(N2(h))

    Attn(x):  c_q = N_q(x W_qa);  q = c_q W_qb -> heads of [nope | rope]
              [c_kv | k_r] = x W_kva;  [k_nope | v] = N_kv(c_kv) W_kvb
              rotary (rotate-half, `rope_theta`, positions 0..L-1) on each
              head's rope part of q and ONCE on k_r, which every head
              shares;  k = [k_nope | k_r]
              o = softmax(q k^T / sqrt(nope + rope), causal) v;  o W_o
    MoE(x):   s = sigmoid(x W_r) in float32 over all the experts, the
              `top_k` largest, w = scaling s / sum of the picked; SwiGLU
              experts plus one shared SwiGLU expert
    MTP:      h' = [N_h(h) ; N_e(Emb(t_{i+1}))] W_eh with h the stream
              BEFORE the final norm; h'' = Block(h'); N_out(h'') through
              the model's own head against t_{i+2};
              loss = main + mtp_weight x mtp   (arXiv:2412.19437, eq. 21-25)

The mixer is `models/decoder.LatentMixer` (Kimi's too, which gives it
neither a query rank nor positions); the block, the router, the expert
layer, the model, the module and the blocked losses are
`models/decoder.py`'s.

Scopes (telemetry/layers.SCOPES): ``mla/proj`` (both low-rank paths, their
norms, rotary, the product out), ``mla/attention`` (around
`fused_attention`, whose ``attn/core`` nests inside), ``moe/route``,
``moe/experts`` (with ``moe/plan`` and ``moe/dispatch``), ``moe/shared``,
``lm/loss``, and ``mtp/module`` around everything the module runs (its
``mtp/combine``, its block's ``mla/*`` and ``moe/*``, its ``lm/loss``).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

from geomx_tpu.models.decoder import DecoderLM, LatentMixer


@dataclasses.dataclass(frozen=True)
class Glm4MoeLiteConfig:
    """``layers``: one (mixer, ffn) pair a block, mixer "mla", ffn "mlp" |
    "moe"; e.g. the dense lead and four expert layers:
    (("mla", "mlp"),) + (("mla", "moe"),) * 4.  ``mtp_depth`` modules of
    one ``mtp_block`` each follow the last block; their loss counts
    ``mtp_weight`` times."""
    vocab: int
    hidden: int
    layers: Tuple[Tuple[str, str], ...]
    num_heads: int
    q_rank: int
    kv_rank: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_head_dim: int
    rope_theta: float
    dense_width: int
    expert_width: int
    num_experts: int
    experts_held: int
    expert_offset: int
    top_k: int
    routed_scaling: float
    shared_experts: int = 1
    mtp_depth: int = 1
    mtp_weight: float = 0.3
    mtp_block: Tuple[str, str] = ("mla", "moe")
    eps: float = 1e-5
    loss_block: int = 2048
    expert_rows: int = 512
    expert_pool: Optional[int] = None   # None: 2 x held x expert_rows places
    remat: bool = True

    post_norms = False          # one norm a half, before it
    embedding_scale = 1.0
    expert_form = {}            # SwiGLU experts in the hidden width

    def make_mixer(self, kind: str, dtype):
        if kind != "mla":
            raise ValueError(f"no mixer {kind!r}")
        return LatentMixer(self.num_heads, self.qk_nope_dim, self.qk_rope_dim,
                           self.v_head_dim, self.kv_rank, self.eps, dtype,
                           self.q_rank, self.rope_theta, name="core")


class Glm4MoeLiteLM(DecoderLM):
    """`models/decoder.DecoderLM` under a `Glm4MoeLiteConfig`."""
