"""A looped causal decoder (`model_type` ``ouro``; arXiv:2510.25741,
"Scaling Latent Reasoning via Looped Language Models"): a stack of dense
sandwich-norm blocks applied ``loops`` times to the same stream with the
same parameters, the head read after every pass, and the passes' losses
mixed by a learned per-token exit distribution.

    h += N2(Attn(N1(h)));  h += N4(MLP(N3(h)))            (a block)
    h^0 = Emb(x);  h^t = N_f(Blocks(h^(t-1))),  t = 1..T  (the loop)
    z^t = h^t W_head;  lambda^t = sigmoid(h^t . w_g + b_g)
    p^t = lambda^t prod_{j<t} (1 - lambda^j),  p^T the rest
    L = mean_i [ sum_t p^t_i ce^t_i - exit_beta H(p_i) ]

    Attn(x):  q, k, v = x Wq, x Wk, x Wv   (no biases, no q/k norm, no
              gate), rotate-half rotary over the whole head on every
              layer, o = softmax(q k^T / sqrt(d)) v over every earlier
              key;  o Wo
    MLP(x):   SwiGLU, no bias

The mixer is `models/afmoe.GQAMixer` with its per-head norms and its gate
left out; the block, the loop, the exit gate, the model and its blocked,
weighted next-token loss are `models/decoder.py`'s (`DecoderLM.features`,
`DecoderLM.looped_loss`).

Scopes (telemetry/layers.SCOPES): ``gqa/proj`` (the three products in,
rotary, the product out), ``gqa/global`` (around `fused_attention`, whose
``attn/core`` nests inside), ``ffn/mlp``, ``loop/exit``, ``lm/loss`` (the T
head passes).
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

from geomx_tpu.models.afmoe import GQAMixer
from geomx_tpu.models.decoder import DecoderLM


@dataclasses.dataclass(frozen=True)
class OuroConfig:
    """``layers``: one (mixer, ffn) pair a block, every one ("global",
    "mlp").  ``loops``: how often the stack runs a step (1: a plain
    decoder with no gate); ``exit_beta``: the entropy term's coefficient."""
    vocab: int
    hidden: int
    layers: Tuple[Tuple[str, str], ...]
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float
    dense_width: int
    loops: int = 4
    exit_beta: float = 0.05
    eps: float = 1e-6
    loss_block: int = 2048
    remat: bool = True

    post_norms = True           # N2 and N4: a norm after each half too
    embedding_scale = 1.0

    def __post_init__(self):
        if any(ffn != "mlp" for _, ffn in self.layers):
            raise ValueError("every feed-forward half is a dense MLP here")

    def make_mixer(self, kind: str, dtype):
        if kind != "global":
            raise ValueError(f"no mixer {kind!r}")
        return GQAMixer(self.num_heads, self.num_kv_heads, self.head_dim,
                        None, self.rope_theta, self.eps, dtype, gated=False,
                        qk_norm=False, name="core")


class OuroLM(DecoderLM):
    """`models/decoder.DecoderLM` under an `OuroConfig`."""
