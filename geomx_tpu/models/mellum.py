"""A causal decoder of Mellum blocks (`model_type` ``mellum``): plain
pre-norm blocks of grouped-query attention and an expert layer with
nothing beside its routed experts,

    h += Attn(N1(h));  h += MoE(N2(h))

    Attn(x):  q, k, v = x Wq, x Wk, x Wv   (no biases, no gate)
              q, k normalised per head (RMSNorm over the head, one learned
              scale each), then rotary on EVERY layer, from a table per
              layer kind: window layers (a causal band of `window` keys)
              plain rotary at `rope_theta`; global layers (every earlier
              key) YaRN's blended frequencies with its factor on cos and
              sin (`ops/gqa_elementwise.Yarn`)
              o = softmax(q k^T / sqrt(d)) v;  o Wo
    MoE(x):   p = softmax(x Wr) in float32 over all the experts, the
              `top_k` largest, w = p / sum of the picked; SwiGLU experts,
              no shared expert

The mixer is `models/afmoe.GQAMixer` (its positions a table per kind, its
gate left out); the block, the router, the expert layer, the model and its
blocked next-token loss are `models/decoder.py`'s.

Scopes (telemetry/layers.SCOPES): ``gqa/proj`` (the three products in, the
q/k norms with rotary, the product out), ``gqa/window`` and ``gqa/global``
(around `fused_attention`, whose ``attn/core`` nests inside), ``moe/route``,
``moe/experts`` (with ``moe/plan`` and ``moe/dispatch``), ``lm/loss``; no
``moe/shared``: there is no shared expert.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

from geomx_tpu.models.afmoe import GQAMixer
from geomx_tpu.models.decoder import DecoderLM
from geomx_tpu.ops.gqa_elementwise import Yarn


@dataclasses.dataclass(frozen=True)
class MellumConfig:
    """``layers``: one (mixer, ffn) pair a block, mixer "window" |
    "global", ffn "moe" (every layer is sparse); e.g. one period:
    (("window", "moe"),) * 3 + (("global", "moe"),).  ``rope_theta``: the
    window layers' positions; ``yarn``: the global layers'."""
    vocab: int
    hidden: int
    layers: Tuple[Tuple[str, str], ...]
    num_heads: int
    num_kv_heads: int
    head_dim: int
    window: int
    rope_theta: float
    expert_width: int
    num_experts: int
    experts_held: int
    expert_offset: int
    top_k: int
    yarn: Yarn
    eps: float = 1e-6
    loss_block: int = 2048
    expert_rows: int = 512
    expert_pool: Optional[int] = None   # None: 2 x held x expert_rows places
    remat: bool = True

    post_norms = False          # one norm a half, before it
    embedding_scale = 1.0
    routed_scaling = 1.0        # `norm_topk_prob`: the picked sum to 1
    shared_experts = 0
    expert_form = {"scoring": "softmax"}

    def make_mixer(self, kind: str, dtype):
        if kind not in ("window", "global"):
            raise ValueError(f"no mixer {kind!r}")
        window = kind == "window"
        return GQAMixer(self.num_heads, self.num_kv_heads, self.head_dim,
                        self.window if window else None,
                        self.rope_theta if window else self.yarn, self.eps,
                        dtype, gated=False, name="core")


class MellumLM(DecoderLM):
    """`models/decoder.DecoderLM` under a `MellumConfig`."""
