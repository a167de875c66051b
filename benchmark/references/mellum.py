"""Plain forward pass and loss of the Mellum decoder (Mellum2), written
from its equations (ISSUE 40, PERF.md section 4): pre-norm blocks,

    h += Attn(N1(h));  h += MoE(N2(h))

a final RMSNorm, an untied head, mean next-token cross-entropy in float32.

- Attn: q, k, v = x Wq, x Wk, x Wv, no biases, no gate; q and k
  normalised per head by an RMSNorm over the head (one learned scale
  each), then rotary on EVERY layer (rotate-half over the whole head,
  positions 0..L-1): cos = f cos(p w_i), sin = f sin(p w_i).  **Window
  layers**: w_i = theta^(-2 i / d), f = 1, key j seen by query i iff
  0 <= i - j < window.  **Global layers**: YaRN's frequencies
  (:func:`yarn_frequencies`) and f = `attention_factor`, causal.  Query
  head n reads key/value head n // group (k and v repeated here);
  softmax(q k^T / sqrt(d)) v, then W_o; dense masked scores, blocked over
  queries.
- MoE: p = softmax(x Wr) in float32 over all the experts, the `top_k`
  largest, w = p / sum of the picked (`norm_topk_prob`, no scaling
  factor); a masked `lax.scan` over the SwiGLU experts held here, every
  token through each; no shared expert; what absent experts would add is
  left out.

Nothing of the program is imported.  Parameters are a nested dict under
the names the configuration's family lists; one layer of one sequence is
rematerialised at a time.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.references.kimi_linear import rms_norm, swiglu
from benchmark.references.numerics import Numerics


def yarn_range(d: int, yarn: dict):
    """(low, high): the ramp's ends among a head's d / 2 pairs.  Pair
    dim(n) = d ln(original / (2 pi n)) / (2 ln theta) turns n times within
    the `original` positions; low = floor(dim(beta_fast)), high =
    ceil(dim(beta_slow)), inside [0, d - 1]."""
    def dim(turns):
        return (d * math.log(yarn["original"] / (2 * math.pi * turns))
                / (2 * math.log(yarn["theta"])))
    return (max(math.floor(dim(yarn["beta_fast"])), 0),
            min(math.ceil(dim(yarn["beta_slow"])), d - 1))


def yarn_frequencies(d: int, yarn: dict) -> np.ndarray:
    """w_i = inter_i ramp_i + extra_i (1 - ramp_i), i = 0..d/2-1, with
    extra_i = theta^(-2 i / d), inter_i = extra_i / factor and ramp_i =
    clip((i - low) / (high - low), 0, 1): the fast pairs keep their
    frequency, the slow ones are stretched `factor` times.  float64 here,
    float32 where it is used."""
    i = np.arange(d // 2, dtype=np.float64)
    extra = yarn["theta"] ** (-2.0 * i / d)
    low, high = yarn_range(d, yarn)
    ramp = np.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    return extra / yarn["factor"] * ramp + extra * (1.0 - ramp)


def frequencies(sizes: dict, mixer: str):
    """(w [d / 2], f) of a layer kind."""
    d = sizes["head_dim"]
    if mixer == "global":
        return yarn_frequencies(d, sizes["yarn"]), \
            sizes["yarn"]["attention_factor"]
    return sizes["rope_theta"] ** (-2.0 * np.arange(d // 2) / d), 1.0


def rotary(x, w, f: float):
    """x [b, L, H, d]: (x1, x2) -> (x1 cos - x2 sin, x2 cos + x1 sin) with
    x1, x2 the head's two halves, cos = f cos(t w_i), sin = f sin(t w_i)."""
    length, half = x.shape[1], x.shape[-1] // 2
    angle = jnp.arange(length, dtype=jnp.float32)[:, None] \
        * jnp.asarray(w, jnp.float32)
    cos, sin = f * jnp.cos(angle)[:, None, :], f * jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(nx: Numerics, x, p, sizes: dict, mixer: str,
              query_block: int = 256):
    b, length, _ = x.shape
    heads, kv, d = sizes["num_heads"], sizes["num_kv_heads"], sizes["head_dim"]
    window = sizes["window"] if mixer == "window" else None

    def project(name, n):
        return nx.einsum("bld,df->blf", x, p[name]).reshape(b, length, n, d)

    w, f = frequencies(sizes, mixer)
    q = rotary(rms_norm(project("q_kernel", heads), p["q_norm"],
                        sizes["eps"]), w, f)
    k = rotary(rms_norm(project("k_kernel", kv), p["k_norm"],
                        sizes["eps"]), w, f)
    v = project("v_kernel", kv)
    k, v = (jnp.repeat(y, heads // kv, axis=2) for y in (k, v))
    block = min(query_block, length)
    pad = (-length) % block
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    key_at = jnp.arange(length)

    @jax.checkpoint
    def queries(args):
        q_, start = args
        s = nx.einsum("bqhe,bkhe->bhqk", q_, k) / math.sqrt(d)
        back = (start + jnp.arange(block))[:, None] - key_at[None, :]
        seen = back >= 0
        if window is not None:
            seen = seen & (back < window)
        weights = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return nx.einsum("bhqk,bkhe->bqhe", weights, v)

    starts = jnp.arange(0, length + pad, block)
    o = jax.lax.map(queries, (
        jnp.moveaxis(qp.reshape(b, -1, block, heads, d), 1, 0), starts))
    o = jnp.moveaxis(o, 0, 1).reshape(b, length + pad, -1)[:, :length]
    return nx.einsum("blf,fd->bld", o, p["out_kernel"])


def routing(nx: Numerics, x, router, top_k: int):
    """x [T, d] -> [T, num_experts]: each token's weight for every expert,
    zero where it was not picked; a token's weights sum to 1."""
    p = jax.nn.softmax(nx.einsum("td,de->te", x, router), axis=-1)
    picked, chosen = jax.lax.top_k(p, top_k)
    weights = picked / jnp.sum(picked, -1, keepdims=True)
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(p).at[rows, chosen].set(weights)


def moe(nx: Numerics, x, p, offset: int, top_k: int):
    tokens = x.reshape(-1, x.shape[-1])
    held = p["experts_gate_kernel"].shape[0]
    weights = routing(nx, tokens, p["router_kernel"], top_k)
    one = jax.checkpoint(lambda w, a, b, c: w[:, None]
                         * swiglu(nx, tokens, a, b, c))
    y, _ = jax.lax.scan(
        lambda y, xs: (y + one(*xs), None), jnp.zeros_like(tokens),
        (weights[:, offset:offset + held].T, p["experts_gate_kernel"],
         p["experts_up_kernel"], p["experts_down_kernel"]))
    return y.reshape(x.shape)


def block(nx: Numerics, h, p, mixer: str, sizes: dict):
    eps = sizes["eps"]
    h = h + attention(nx, rms_norm(h, p["mixer"]["norm"], eps),
                      p["mixer"]["core"], sizes, mixer)
    return h + moe(nx, rms_norm(h, p["ffn"]["norm"], eps), p["ffn"]["core"],
                   sizes["expert_offset"], sizes["top_k"])


def features(params, tokens, sizes: dict, nx: Numerics):
    """One layer is rematerialised at a time, and within a layer one
    sequence at a time (`lax.map` over the batch), so that the check fits
    beside the weights, Adam's moments and the gradient."""
    h = params["embedding"][tokens.astype(jnp.int32)]
    for i, (mixer, _) in enumerate(sizes["layers"]):     # every ffn "moe"
        p = params[f"layer{i + 1}"]
        one = jax.checkpoint(
            lambda row, p_, m=mixer: block(nx, row[None], p_, m, sizes)[0])
        h = jax.lax.map(lambda row, p_=p, one_=one: one_(row, p_), h)
    return rms_norm(h, params["final_norm"], sizes["eps"])


def logits(params, tokens, sizes: dict, nx: Numerics):
    return nx.einsum("bld,dv->blv", features(params, tokens, sizes, nx),
                     params["head_kernel"])


def loss(params, tokens, labels, sizes: dict, nx: Numerics,
         token_block: int = 2048):
    """Mean cross-entropy of `labels` [b, L], the logits a block of tokens
    at a time."""
    h = features(params, tokens, sizes, nx)
    h = h.reshape(-1, h.shape[-1])
    y = labels.reshape(-1).astype(jnp.int32)
    total = h.shape[0]
    step = min(token_block, total)
    pad = (-total) % step
    h = jnp.pad(h, ((0, pad), (0, 0)))
    y = jnp.pad(y, (0, pad), constant_values=-1)

    @jax.checkpoint
    def some(carry, xs):
        h_, y_ = xs
        z = nx.einsum("td,dv->tv", h_, params["head_kernel"])
        picked = jnp.take_along_axis(z, jnp.maximum(y_, 0)[:, None], -1)[:, 0]
        each = jax.nn.logsumexp(z, axis=-1) - picked
        return carry + jnp.sum(jnp.where(y_ >= 0, each, 0.0)), None

    summed, _ = jax.lax.scan(some, jnp.zeros((), jnp.float32),
                             (h.reshape(-1, step, h.shape[-1]),
                              y.reshape(-1, step)))
    return summed / total
