"""A causal decoder of Kimi-Linear blocks: Kimi Delta Attention (KDA,
`ops/kda.py`; on a TPU the kernels of `ops/kda_pallas.py`) and latent
attention (MLA: `models/decoder.LatentMixer` with one W_q and no
positions, 192-wide q/k and 128-wide v) as mixers, a SwiGLU MLP or an
expert layer that holds some of its experts (`ops/held_experts.py`) as
feed-forward, pre-RMSNorm residual blocks, an untied head.

    h += Mixer(RMSNorm(h));  h += FFN(RMSNorm(h))

This file holds the KDA mixer and the configuration; the latent mixer
(shared with `models/glm4_moe_lite.py`), the block, the feed-forward half,
the router, the model and its blocked next-token loss (`loss_and_aux`) are
`models/decoder.py`'s, shared with `models/afmoe.py`.

Upstream's initialisation of the decay gate is not a zero-mean normal:
``A_log`` starts at log U(1, 16) and ``dt_bias`` at the inverse softplus
of a step in [0.001, 0.1].  The parameters here are offsets from the
centres of those ranges (`A_LOG_CENTRE`, `DT_BIAS_CENTRE`), so that
seeded zero-mean weights decay like a trained layer does (exp(-0.07) a
token at the centre) and not by half a token.

Scopes (telemetry/layers.SCOPES): ``kda/proj``, ``kda/scan``,
``mla/proj``, ``mla/attention``, ``moe/route``, ``moe/experts``,
``moe/shared``, ``lm/loss``.  Module names are ``mixer``, ``ffn``, ``core``
and ``norm`` so that flax's own name stack never reads as one of them.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from geomx_tpu.models.decoder import (MLP, Block, DecoderLM,  # noqa: F401
                                      FFNBranch, HeldExpertsLayer,
                                      LatentMixer, MixerBranch, RMSNorm,
                                      _fan_in, _normal,
                                      blocked_cross_entropy, causal_conv,
                                      route, swiglu)
from geomx_tpu.ops import dispatch
from geomx_tpu.utils.profiler import profile_scope

A_LOG_CENTRE = 1.96          # mean of log U(1, 16)
DT_BIAS_CENTRE = -4.6        # inverse softplus of 0.01


class KDAMixer(nn.Module):
    """Parameters are stored as the published matrices ([hidden, heads x
    head], ...); the products write heads-major activations [B, H, L, e]
    directly, the layout the scan (`ops.dispatch.kda`) cuts into chunks
    without moving data (a [L, H x e] -> [L, H, e] reshape of an
    activation is a copy on a TPU: the tiled minor dimensions change)."""
    num_heads: int
    head_dim: int
    conv_size: int
    eps: float
    chunk: int = 64
    sub: int = 16
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        h, d, dt = self.num_heads, self.head_dim, self.dtype
        width, hidden = h * d, x.shape[-1]
        mat = lambda name, shape: self.param(name, _fan_in, shape)
        heads = lambda w: w.reshape(w.shape[0], h, d).astype(dt)
        with profile_scope("kda/proj", "compute"):
            def branch(name):
                y = jnp.einsum("bld,dhe->bhle", x,
                               heads(mat(name + "_kernel", (hidden, width))))
                conv = self.param(name + "_conv", _normal(0.5),
                                  (self.conv_size, width))
                return jax.nn.silu(causal_conv(
                    y, conv.reshape(self.conv_size, h, d).astype(dt)))

            def unit(y):
                y = y.astype(jnp.float32)
                return y * lax.rsqrt(
                    jnp.sum(jnp.square(y), -1, keepdims=True) + 1e-6)

            q, k, v = unit(branch("q")) * d ** -0.5, unit(branch("k")), \
                branch("v")
            low = lambda name: jnp.einsum(
                "blr,rhe->bhle",
                jnp.dot(x, mat(name + "_down", (hidden, d)).astype(dt)),
                heads(mat(name + "_up", (d, width))),
                preferred_element_type=jnp.float32)
            a_log = self.param("A_log", _normal(0.5), (h,))
            dt_bias = self.param("dt_bias", _normal(1.0), (width,))
            g = -jnp.exp(A_LOG_CENTRE + a_log)[:, None, None] \
                * jax.nn.softplus(low("f") + DT_BIAS_CENTRE
                                  + dt_bias.reshape(h, 1, d))
            beta = jax.nn.sigmoid(jnp.einsum(
                "bld,dh->bhl", x, mat("beta_kernel", (hidden, h)).astype(dt),
                preferred_element_type=jnp.float32))
            gate = jax.nn.sigmoid(low("g"))
        o = dispatch.kda(q, k, v, g, beta, chunk=self.chunk, sub=self.sub,
                         dtype=dt)
        with profile_scope("kda/proj", "compute"):
            o = RMSNorm(self.eps, name="out_norm")(o) * gate
            out = mat("out_kernel", (width, hidden)).reshape(h, d, hidden)
            return jnp.einsum("bhle,hed->bld", o.astype(dt), out.astype(dt))


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    """``layers``: one (mixer, ffn) pair a block, mixer "kda" | "mla", ffn
    "mlp" | "moe"; e.g. the dense layer and one period:
    (("kda", "mlp"), ("kda", "moe"), ("kda", "moe"), ("mla", "moe"),
    ("kda", "moe"))."""
    vocab: int
    hidden: int
    layers: Tuple[Tuple[str, str], ...]
    num_heads: int
    kda_head_dim: int
    conv_size: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_head_dim: int
    kv_rank: int
    dense_width: int
    expert_width: int
    num_experts: int
    experts_held: int
    expert_offset: int
    top_k: int
    routed_scaling: float
    shared_experts: int = 1
    eps: float = 1e-5
    kda_chunk: int = 64
    kda_sub: int = 16
    loss_block: int = 2048
    expert_rows: int = 512
    remat: bool = True

    post_norms = False          # pre-norm residual halves, nothing after
    embedding_scale = 1.0
    expert_pool = None          # first pool: 2 x held x expert_rows places
    expert_form = {}            # SwiGLU experts in the hidden width

    def make_mixer(self, kind: str, dtype):
        if kind == "kda":
            return KDAMixer(self.num_heads, self.kda_head_dim, self.conv_size,
                            self.eps, self.kda_chunk, self.kda_sub, dtype,
                            name="core")
        # one W_q and no positions at all (`q_lora_rank` null, `mla_use_nope`)
        return LatentMixer(self.num_heads, self.qk_nope_dim, self.qk_rope_dim,
                           self.v_head_dim, self.kv_rank, self.eps, dtype,
                           name="core")


class KimiLinearLM(DecoderLM):
    """`models/decoder.DecoderLM` under a `KimiLinearConfig`."""
