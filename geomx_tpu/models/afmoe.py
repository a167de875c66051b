"""A causal decoder of AFMoE blocks (Trinity's `model_type`): gated
grouped-query attention, in a causal band of `window` keys with rotary
positions ("window" layers) or over every earlier key with no positions
at all ("global" layers), through `ops/flash_attention.py` with k and v at
their own head count; a SwiGLU MLP or an expert layer that holds some of
its experts as feed-forward; four RMSNorms a block, one before and one
after each half; the embedding scaled by `embedding_scale`; an untied head.

    a = h + N2(Attn(N1(h)));  h' = a + N4(FFN(N3(a)))

    Attn(x):  q, k, v, g = x Wq, x Wk, x Wv, x Wg   (no biases)
              q, k normalised per head (RMSNorm over the head, one learned
              scale each); window layers: rotary on q and k (rotate-half
              over the whole head, positions 0..L-1)
              o = softmax(q k^T / sqrt(d)) v * sigmoid(g);  o Wo

This file holds the mixer and the configuration; the block, the
feed-forward half, the router, the model and its blocked next-token loss
are `models/decoder.py`'s, shared with `models/kimi_linear.py`.  The mixer
is `models/mellum.py`'s too, which gives both kinds of layer positions (a
table per kind) and no gate, and `models/ouro.py`'s, which also leaves the
q/k norms out.

Scopes (telemetry/layers.SCOPES): ``gqa/proj`` (the four products in, the
q/k norms, rotary, the gate, the product out), ``gqa/window`` and
``gqa/global`` (around `fused_attention`, whose ``attn/core`` nests
inside), and the shared ``moe/route``, ``moe/experts``, ``moe/shared``,
``lm/loss``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax.numpy as jnp

from geomx_tpu.models.decoder import DecoderLM, HeadScale, _fan_in, turn
from geomx_tpu.ops import dispatch
from geomx_tpu.ops.flash_attention import fused_attention
from geomx_tpu.ops.gqa_elementwise import gated_ref, rotary_tables
from geomx_tpu.utils.profiler import profile_scope


def rotary(x, theta: float):
    """Rotate-half rotary positions over the whole head, positions
    0..L-1, angles and arithmetic in float32.  x [B, L, H, d].  The plain
    form: the mixer's own pass is `ops/gqa_elementwise.norm_rotary`, which
    `tests/test_gqa_elementwise.py` and `tools/gqa_proj_timing.py` hold to
    `decoder.RMSNorm` followed by this."""
    length, d = x.shape[1], x.shape[-1]
    half = d // 2
    inverse = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / d)
    angle = jnp.arange(length, dtype=jnp.float32)[:, None] * inverse[None, :]
    cos, sin = (jnp.concatenate([f(angle)] * 2, -1)[None, :, None, :]
                for f in (jnp.cos, jnp.sin))
    x32 = x.astype(jnp.float32)
    turned = jnp.concatenate([-x32[..., half:], x32[..., :half]], -1)
    return (x32 * cos + turned * sin).astype(x.dtype)


class GQAMixer(nn.Module):
    """``window`` None: a global layer (causal over every earlier key);
    else a window layer (key j seen by query i iff 0 <= i - j < window).
    ``rope``: the layer's positions, rotate-half rotary on q and k from
    the tables `ops/gqa_elementwise.rotary_tables` makes of it: None (no
    positions at all), a theta, or a ``gqa_elementwise.Yarn``.  ``gated``
    False: no gate kernel, the core's output goes to W_o as it is.
    ``qk_norm`` False: q and k are not normalised (no ``q_norm`` /
    ``k_norm`` scales), rotary alone through `decoder.turn`.  k and
    v keep their ``num_kv_heads`` heads all the way into the kernels:
    query head n reads key/value head n // (heads / kv heads)."""
    num_heads: int
    num_kv_heads: int
    head_dim: int
    window: Optional[int]
    rope: Any
    eps: float
    dtype: Any = jnp.float32
    gated: bool = True
    qk_norm: bool = True

    @nn.compact
    def __call__(self, x):
        h, kv, d, dt = self.num_heads, self.num_kv_heads, self.head_dim, \
            self.dtype
        b, length, hidden = x.shape
        mat = lambda name, shape: self.param(name, _fan_in, shape).astype(dt)
        with profile_scope("gqa/proj", "compute"):
            def heads(name, n):
                return jnp.dot(x, mat(name, (hidden, n * d))).reshape(
                    b, length, n, d)

            q, k = heads("q_kernel", h), heads("k_kernel", kv)
            if self.qk_norm:
                q, k = dispatch.gqa_norm_rotary(
                    q, k, HeadScale(name="q_norm")(d),
                    HeadScale(name="k_norm")(d), self.eps, self.rope)
            elif self.rope is not None:
                cos, sin = rotary_tables(length, d, self.rope)
                q, k = (turn(y, cos[:, None], sin[:, None]) for y in (q, k))
            v = heads("v_kernel", kv)
            if self.gated:
                gate = jnp.dot(x, mat("gate_kernel", (hidden, h * d)),
                               preferred_element_type=jnp.float32)
        with profile_scope("gqa/global" if self.window is None
                           else "gqa/window", "kernel"):
            o = fused_attention(q, k, v, True, False, self.window)
        with profile_scope("gqa/proj", "compute"):
            o = o.reshape(b, length, h * d)
            if self.gated:
                o = gated_ref(o, gate)
            return jnp.dot(o, mat("out_kernel", (h * d, hidden)))


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    """``layers``: one (mixer, ffn) pair a block, mixer "window" |
    "global", ffn "mlp" | "moe"; e.g. the dense layer and one period:
    (("window", "mlp"), ("window", "moe"), ("global", "moe"),
    ("window", "moe"), ("window", "moe"))."""
    vocab: int
    hidden: int
    layers: Tuple[Tuple[str, str], ...]
    num_heads: int
    num_kv_heads: int
    head_dim: int
    window: int
    rope_theta: float
    dense_width: int
    expert_width: int
    num_experts: int
    experts_held: int
    expert_offset: int
    top_k: int
    routed_scaling: float
    shared_experts: int = 1
    embedding_scale: float = 1.0
    eps: float = 1e-5
    loss_block: int = 2048
    expert_rows: int = 512
    expert_pool: Optional[int] = None   # None: 2 x held x expert_rows places
    remat: bool = True

    post_norms = True           # N2 and N4: a norm after each half too
    expert_form = {}            # SwiGLU experts in the hidden width

    def make_mixer(self, kind: str, dtype):
        if kind not in ("window", "global"):
            raise ValueError(f"no mixer {kind!r}")
        window = kind == "window"       # rotary on window layers only
        return GQAMixer(self.num_heads, self.num_kv_heads, self.head_dim,
                        self.window if window else None,
                        self.rope_theta if window else None, self.eps, dtype,
                        name="core")


class AfmoeLM(DecoderLM):
    """`models/decoder.DecoderLM` under an `AfmoeConfig`."""
