"""A causal decoder of Nemotron-H layers (NVIDIA's `model_type`
`nemotron_h`): every layer is ONE half with one RMSNorm and one residual
add, `h += F(RMSNorm(h))`, and F is a Mamba-2 state-space mixer ("M"), a
plain grouped-query attention ("*") or a LatentMoE ("E"); a final RMSNorm;
an untied head.

    M:  [z, xBC, dt] = x W_in;  xBC = silu(conv4(xBC) + bias)
        X [H, P], B, C [G, N] = split(xBC);  dt = softplus(dt + dt_bias)
        S_t = exp(-exp(A_log) dt_t) S_(t-1) + dt_t X_t B_t^T   (a head;
        B, C of the head's group);  Y_t = S_t C_t + D X_t
        out = GroupRMSNorm(Y * silu(z)) W_out     (a group's channels)
    *:  q, k, v = x Wq, x Wk, x Wv;  causal softmax(q k^T / sqrt(d)) v;
        W_o.  No positions (the Mamba layers carry them), no gate, no q/k
        norm, no bias.
    E:  `decoder.HeldExpertsLayer` with ``latent``, un-gated squared-ReLU
        experts and a shared expert of its own width.

A mixer is told how many heads and groups it HOLDS: a chip's share of a
layer divided over chips by heads (Mamba-2: whole B/C groups with their
heads and their gated-norm group; attention: query heads with the
key/value head they read) runs here without its exchange, and the sum of
all shares' outputs is the whole layer's (`tests/test_nemotron_h.py`).

Upstream's initialisation of the decay is not a zero-mean normal:
``A_log`` starts at log U(1, 16), ``dt_bias`` at the inverse softplus of
a step in [time_step_min, time_step_max] = [0.001, 0.1], ``D`` at ones.
The parameters here are offsets from the centres of those (`A_LOG_CENTRE`,
`DT_BIAS_CENTRE`, `D_CENTRE`), as `models/kimi_linear.py` keeps its decay
gate's, so that seeded zero-mean weights decay like a trained layer does
(exp(-0.07) a token at the centre).

This file holds the two mixers and the configuration; the block, the
expert layer, the router, the model and its blocked next-token loss are
`models/decoder.py`'s, shared with `models/kimi_linear.py` and
`models/afmoe.py`.

Scopes (telemetry/layers.SCOPES): ``ssd/proj`` (in- and out-projection,
convolution, gated norm), ``ssd/scan`` (`ops/ssd.py`), ``gqa/proj``,
``gqa/global`` (around `fused_attention`, whose ``attn/core`` nests
inside), and the shared ``moe/route``, ``moe/experts``, ``moe/shared``,
``moe/latent``, ``lm/loss``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from geomx_tpu.models.decoder import (DecoderLM, HeadScale, _fan_in,
                                      _normal, causal_conv)
from geomx_tpu.ops import dispatch
from geomx_tpu.ops.flash_attention import fused_attention
from geomx_tpu.utils.profiler import profile_scope

A_LOG_CENTRE = 1.96          # mean of log U(1, 16)
DT_BIAS_CENTRE = -4.6        # inverse softplus of 0.01
D_CENTRE = 1.0


class Mamba2Mixer(nn.Module):
    """``num_heads`` heads of ``head_dim`` in ``num_groups`` B/C groups of
    ``state`` channels: what is held here.  The gated RMSNorm is over each
    group's ``num_heads / num_groups x head_dim`` channels."""
    num_heads: int
    head_dim: int
    num_groups: int
    state: int
    conv_size: int
    eps: float
    chunk: int = 128
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        h, p, g, n, dt = self.num_heads, self.head_dim, self.num_groups, \
            self.state, self.dtype
        b, length, hidden = x.shape
        inner, bc = h * p, g * n
        mat = lambda name, shape: self.param(name, _fan_in, shape).astype(dt)
        f32 = jnp.float32
        with profile_scope("ssd/proj", "compute"):
            z, xbc, step = jnp.split(
                jnp.dot(x, mat("in_kernel", (hidden, 2 * inner + 2 * bc + h))),
                [inner, 2 * inner + 2 * bc], axis=-1)
            taps = self.param("conv_kernel", _normal(0.5),
                              (self.conv_size, inner + 2 * bc)).astype(dt)
            bias = self.param("conv_bias", _normal(0.2),
                              (inner + 2 * bc,)).astype(dt)
            xbc = jax.nn.silu(
                causal_conv(xbc[:, None], taps[:, None])[:, 0] + bias)
            xs, bs, cs = jnp.split(xbc, [inner, inner + bc], axis=-1)
            xs = xs.reshape(b, length, h, p)
            step = jax.nn.softplus(
                step.astype(f32) + DT_BIAS_CENTRE
                + self.param("dt_bias", _normal(1.0), (h,)))
            decay = -jnp.exp(A_LOG_CENTRE
                             + self.param("A_log", _normal(0.5), (h,)))
            skip = D_CENTRE + self.param("D", _normal(0.25), (h,))
        y = dispatch.ssd(xs, step, decay, bs.reshape(b, length, g, n),
                         cs.reshape(b, length, g, n), self.chunk, dt)
        with profile_scope("ssd/proj", "compute"):
            y = y + skip[:, None] * xs.astype(f32)
            y = (y.reshape(b, length, g, inner // g)
                 * jax.nn.silu(z.astype(f32)).reshape(b, length, g, -1))
            y = y * lax.rsqrt(
                jnp.mean(jnp.square(y), -1, keepdims=True) + self.eps)
            y = y.reshape(b, length, inner) * HeadScale(name="out_norm")(inner)
            return jnp.dot(y.astype(dt), mat("out_kernel", (inner, hidden)))


class AttentionMixer(nn.Module):
    """Causal grouped-query attention over every earlier key, nothing
    else: query head n reads key/value head n // (heads / kv heads)."""
    num_heads: int
    num_kv_heads: int
    head_dim: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        h, kv, d, dt = self.num_heads, self.num_kv_heads, self.head_dim, \
            self.dtype
        b, length, hidden = x.shape
        mat = lambda name, shape: self.param(name, _fan_in, shape).astype(dt)
        with profile_scope("gqa/proj", "compute"):
            heads = lambda name, n: jnp.dot(
                x, mat(name, (hidden, n * d))).reshape(b, length, n, d)
            q, k, v = heads("q_kernel", h), heads("k_kernel", kv), \
                heads("v_kernel", kv)
        with profile_scope("gqa/global", "kernel"):
            o = fused_attention(q, k, v, True)
        with profile_scope("gqa/proj", "compute"):
            return jnp.dot(o.reshape(b, length, h * d),
                           mat("out_kernel", (h * d, hidden)))


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    """``layers``: one (mixer, ffn) pair a layer with ONE of them None,
    mixer "mamba" | "attention", ffn "moe"; e.g. one period of the
    published pattern ``MEMEMEMEM*E``: (("mamba", None), (None, "moe"),
    ..., ("attention", None), (None, "moe")).  Head and group counts are
    what is HELD here."""
    vocab: int
    hidden: int
    layers: Tuple[Tuple[Optional[str], Optional[str]], ...]
    mamba_heads: int
    mamba_head_dim: int
    mamba_groups: int
    state_size: int
    conv_size: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    expert_width: int
    shared_width: int
    latent: int
    num_experts: int
    experts_held: int
    expert_offset: int
    top_k: int
    routed_scaling: float
    shared_experts: int = 1
    eps: float = 1e-5
    ssd_chunk: int = 128
    loss_block: int = 2048
    expert_rows: int = 512
    expert_pool: Optional[int] = None   # None: 2 x held x expert_rows places
    remat: bool = True

    post_norms = False          # one norm a layer, before its half
    embedding_scale = 1.0

    @property
    def expert_form(self):
        return {"gated": False, "latent": self.latent,
                "shared_width": self.shared_width}

    def make_mixer(self, kind: str, dtype):
        if kind == "mamba":
            return Mamba2Mixer(self.mamba_heads, self.mamba_head_dim,
                               self.mamba_groups, self.state_size,
                               self.conv_size, self.eps, self.ssd_chunk,
                               dtype, name="core")
        if kind != "attention":
            raise ValueError(f"no mixer {kind!r}")
        return AttentionMixer(self.num_heads, self.num_kv_heads,
                              self.head_dim, dtype, name="core")


class NemotronHLM(DecoderLM):
    """`models/decoder.DecoderLM` under a `NemotronHConfig`."""
