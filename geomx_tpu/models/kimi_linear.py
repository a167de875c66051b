"""A causal decoder of Kimi-Linear blocks: Kimi Delta Attention (KDA,
`ops/kda.py`; on a TPU the kernels of `ops/kda_pallas.py`) and latent
attention (MLA, through `ops/flash_attention.py` with 192-wide q/k and
128-wide v) as mixers, a SwiGLU MLP or an expert layer that holds some of
its experts (`ops/held_experts.py`) as feed-
forward, pre-RMSNorm residual blocks, an untied head.

    h += Mixer(RMSNorm(h));  h += FFN(RMSNorm(h))

The model brings its own loss (`loss_and_aux`): mean next-token
cross-entropy in float32, blocked over tokens so that no whole logits
array lives; `train/step.make_loss_fn` takes it from there.  In the
backward pass each block's mixer is rematerialised a sequence at a time and
its feed-forward half on its own.

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

from geomx_tpu.ops import dispatch
from geomx_tpu.ops.flash_attention import fused_attention
from geomx_tpu.ops.held_experts import held_experts
from geomx_tpu.utils.profiler import profile_scope

A_LOG_CENTRE = 1.96          # mean of log U(1, 16)
DT_BIAS_CENTRE = -4.6        # inverse softplus of 0.01
_HIGHEST = lax.Precision.HIGHEST


class RMSNorm(nn.Module):
    eps: float

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        y = x32 * lax.rsqrt(
            jnp.mean(jnp.square(x32), -1, keepdims=True) + self.eps)
        return (y * scale).astype(x.dtype)


def _normal(std: float = 0.02):
    return nn.initializers.normal(std)


def _fan_in(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype) * shape[-2] ** -0.5


def causal_conv(x, kernel):
    """Depthwise causal convolution over time, heads-major: x [B, H, L,
    e], kernel [taps, H, e]; tap ``taps - 1`` multiplies the current
    token."""
    taps, length = kernel.shape[0], x.shape[2]
    padded = jnp.pad(x, ((0, 0), (0, 0), (taps - 1, 0), (0, 0)))
    return sum(padded[:, :, j:j + length] * kernel[j][:, None, :]
               for j in range(taps))


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


class MLAMixer(nn.Module):
    num_heads: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    kv_rank: int
    eps: float
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        h, dt, hidden = self.num_heads, self.dtype, x.shape[-1]
        b, length, _ = x.shape
        qk = self.nope_dim + self.rope_dim
        mat = lambda name, shape: self.param(name, _fan_in, shape)
        with profile_scope("mla/proj", "compute"):
            q = jnp.dot(x, mat("q_kernel", (hidden, h * qk)).astype(dt))
            kv = jnp.dot(x, mat("kv_a_kernel",
                                (hidden, self.kv_rank + self.rope_dim))
                         .astype(dt))
            latent = RMSNorm(self.eps, name="kv_norm")(
                kv[..., :self.kv_rank])
            shared = kv[..., self.kv_rank:]        # one key part, all heads
            kv_b = jnp.dot(latent, mat(
                "kv_b_kernel", (self.kv_rank, h * (self.nope_dim + self.v_dim))
            ).astype(dt)).reshape(b, length, h, self.nope_dim + self.v_dim)
            k = jnp.concatenate(
                [kv_b[..., :self.nope_dim], jnp.broadcast_to(
                    shared[:, :, None, :], (b, length, h, self.rope_dim))],
                -1)
            v = kv_b[..., self.nope_dim:]
            q = q.reshape(b, length, h, qk)
        with profile_scope("mla/attention", "kernel"):
            o = fused_attention(q, k, v, True)
        with profile_scope("mla/proj", "compute"):
            return jnp.dot(o.reshape(b, length, h * self.v_dim),
                           mat("out_kernel", (h * self.v_dim, hidden))
                           .astype(dt))


def swiglu(x, gate, up, down):
    return jnp.dot(jax.nn.silu(jnp.dot(x, gate)) * jnp.dot(x, up), down)


class MLP(nn.Module):
    width: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        hidden, dt = x.shape[-1], self.dtype
        mat = lambda name, shape: self.param(name, _fan_in, shape).astype(dt)
        return swiglu(x, mat("gate_kernel", (hidden, self.width)),
                      mat("up_kernel", (hidden, self.width)),
                      mat("down_kernel", (self.width, hidden)))


def route(x, router, bias, top_k: int, scaling: float):
    """Sigmoid scores in float32, the ``top_k`` largest of score + bias,
    weights normalised over the selected and scaled.  x [T, d]."""
    scores = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32), router,
                                    precision=_HIGHEST))
    _, idx = lax.top_k(scores + bias, top_k)
    picked = jnp.take_along_axis(scores, idx, axis=-1)
    return idx, scaling * picked / jnp.sum(picked, -1, keepdims=True)


class HeldExpertsLayer(nn.Module):
    """Routes over ``num_experts``, holds ``num_held`` of them from
    ``offset`` on, and computes the shared expert plus its own experts'
    part of the result.  The selection bias is not trained by gradient:
    zeros, outside ``params``.  Returns (y, assignments that arrived at
    each held expert [num_held], assignments dropped: 0)."""
    num_experts: int
    num_held: int
    offset: int
    top_k: int
    width: int
    scaling: float
    shared_experts: int = 1
    rows: int = 512             # assignments a tile of the experts' kernels holds
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        hidden, dt = x.shape[-1], self.dtype
        tokens = x.reshape(-1, hidden)
        mat = lambda name, shape: self.param(name, _fan_in, shape)
        with profile_scope("moe/route", "compute"):
            idx, weights = route(
                tokens, mat("router_kernel", (hidden, self.num_experts)),
                jnp.zeros((self.num_experts,), jnp.float32), self.top_k,
                self.scaling)
        with profile_scope("moe/shared", "compute"):
            wide = self.shared_experts * self.width
            y = swiglu(tokens,
                       mat("shared_gate_kernel", (hidden, wide)).astype(dt),
                       mat("shared_up_kernel", (hidden, wide)).astype(dt),
                       mat("shared_down_kernel", (wide, hidden)).astype(dt))
        with profile_scope("moe/experts", "compute"):
            into = (self.num_held, hidden, self.width)
            routed, counts, dropped = held_experts(
                tokens, idx, weights, mat("experts_gate_kernel", into),
                mat("experts_up_kernel", into),
                mat("experts_down_kernel",
                    (self.num_held, self.width, hidden)),
                self.offset, self.rows)
            y = (y + routed).astype(dt)
        return y.reshape(x.shape), counts, dropped


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


class MixerBranch(nn.Module):
    """``h + Mixer(RMSNorm(h))`` for ONE sequence ``h`` [L, hidden], in
    `nn.scan`'s (carry, x) form: a block runs it sequence by sequence, each
    rematerialised on its own, so that the backward pass holds one
    sequence's mixer internals at a time (at 8,192 tokens a KDA layer's
    are ~2 GB)."""
    kind: str                   # "kda" | "mla"
    cfg: KimiLinearConfig
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, carry, h):
        c, dt = self.cfg, self.dtype
        x = RMSNorm(c.eps, name="norm")(h[None])
        if self.kind == "kda":
            y = KDAMixer(c.num_heads, c.kda_head_dim, c.conv_size, c.eps,
                         c.kda_chunk, c.kda_sub, dt, name="core")(x)
        else:
            y = MLAMixer(c.num_heads, c.qk_nope_dim, c.qk_rope_dim,
                         c.v_head_dim, c.kv_rank, c.eps, dt, name="core")(x)
        return carry, h + y[0]


class FFNBranch(nn.Module):
    """``h + FFN(RMSNorm(h))`` over the whole batch; returns (h,
    assignments that arrived at each held expert, assignments dropped)."""
    kind: str                   # "mlp" | "moe"
    cfg: KimiLinearConfig
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h):
        c, dt = self.cfg, self.dtype
        x = RMSNorm(c.eps, name="norm")(h)
        if self.kind == "mlp":
            return (h + MLP(c.dense_width, dt, name="core")(x),
                    jnp.zeros((0,), jnp.int32), jnp.zeros((), jnp.int32))
        y, counts, dropped = HeldExpertsLayer(
            c.num_experts, c.experts_held, c.expert_offset, c.top_k,
            c.expert_width, c.routed_scaling, c.shared_experts,
            c.expert_rows, dt, name="core")(x)
        return h + y, counts, dropped


class Block(nn.Module):
    mixer: str
    ffn: str
    cfg: KimiLinearConfig
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h):
        remat = nn.remat if self.cfg.remat else (lambda m, **_: m)
        per_sequence = nn.scan(
            remat(MixerBranch, prevent_cse=False),
            variable_broadcast="params", split_rngs={"params": False})
        _, h = per_sequence(self.mixer, self.cfg, self.dtype,
                            name="mixer")((), h)
        return remat(FFNBranch)(self.ffn, self.cfg, self.dtype,
                                name="ffn")(h)


class KimiLinearLM(nn.Module):
    cfg: KimiLinearConfig
    dtype: Any = jnp.float32

    def setup(self):
        c = self.cfg
        self.embedding = self.param("embedding", _normal(0.02),
                                    (c.vocab, c.hidden))
        self.blocks = [Block(mixer, ffn, c, self.dtype, name=f"layer{i + 1}")
                       for i, (mixer, ffn) in enumerate(c.layers)]
        self.final_norm = RMSNorm(c.eps, name="final_norm")
        self.head_kernel = self.param("head_kernel", _fan_in,
                                      (c.hidden, c.vocab))

    def features(self, tokens):
        """(normed features [B, L, hidden], assignments that arrived at
        each held expert of each expert layer, assignments dropped)."""
        h = self.embedding.astype(self.dtype)[tokens.astype(jnp.int32)]
        arrived, dropped = [], jnp.zeros((), jnp.int32)
        for block in self.blocks:
            h, counts, lost = block(h)
            arrived.append(counts)
            dropped = dropped + lost
        return self.final_norm(h), jnp.concatenate(arrived), dropped

    def __call__(self, tokens, train: bool = False):
        """Whole logits [B, L, vocab] in float32: init, eval, small
        inputs.  Training takes `loss_and_aux`."""
        return jnp.dot(self.features(tokens)[0],
                       self.head_kernel.astype(self.dtype),
                       preferred_element_type=jnp.float32)

    def loss_and_aux(self, tokens, labels, train: bool = True):
        """(mean cross-entropy of ``labels`` [B, L], aux).  ``aux`` holds
        ``accuracy`` and, where an expert layer exists, ``counters``:
        scalars a step (assignments per held expert and layer as min,
        mean, max, and those dropped)."""
        h, arrived, dropped = self.features(tokens)
        with profile_scope("lm/loss", "compute"):
            total, hits = blocked_cross_entropy(
                h.reshape(-1, h.shape[-1]),
                self.head_kernel.astype(self.dtype), labels.reshape(-1),
                self.cfg.loss_block)
        aux = {"accuracy": hits / labels.size}
        if arrived.size:
            arrived = arrived.astype(jnp.float32)
            aux["counters"] = {
                "moe/assignments_min": jnp.min(arrived),
                "moe/assignments_mean": jnp.mean(arrived),
                "moe/assignments_max": jnp.max(arrived),
                "moe/dropped": dropped.astype(jnp.float32)}
        return total / labels.size, aux


def blocked_cross_entropy(h, head, labels, block: int):
    """(sum of cross-entropies, number of argmax hits) over tokens h
    [T, d], ``block`` tokens at a time: a block's float32 logits are the
    most that lives, forward and (rematerialised) backward."""
    t = h.shape[0]
    block = min(block, t)
    pad = (-t) % block
    if pad:
        h = jnp.pad(h, ((0, pad), (0, 0)))
        labels = jnp.pad(labels, (0, pad), constant_values=-1)

    @jax.checkpoint
    def one(carry, xs):
        h_, y_ = xs
        logits = jnp.dot(h_, head, preferred_element_type=jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(
            logits, jnp.maximum(y_, 0)[:, None], axis=-1)[:, 0]
        real = y_ >= 0
        hits = jnp.sum(real & (jnp.argmax(logits, -1) == y_))
        return (carry[0] + jnp.sum(jnp.where(real, logz - picked, 0.0)),
                carry[1] + hits.astype(jnp.float32)), None

    (total, hits), _ = lax.scan(
        one, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (h.reshape(-1, block, h.shape[-1]),
         labels.astype(jnp.int32).reshape(-1, block)))
    return total, hits
