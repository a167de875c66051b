"""Plain forward pass and loss of the AFMoE decoder (Trinity), written
from its equations (ISSUE 33, PERF.md section 4): four RMSNorms a block,

    a = h + N2(Attn(N1(h)));  h' = a + N4(FFN(N3(a)))

the embedding times `embedding_scale`, a final RMSNorm, an untied head,
mean next-token cross-entropy in float32.

- Attn: q, k, v, g = x Wq, x Wk, x Wv, x Wg, no biases; q and k
  normalised per head by an RMSNorm over the head (one learned scale
  each); **window layers**: rotary on q and k (rotate-half over the whole
  head, positions 0..L-1) and key j seen by query i iff 0 <= i - j <
  window; **global layers**: no positions, causal; query head n reads
  key/value head n // group (k and v repeated here); softmax(q k^T /
  sqrt(d)) v * sigmoid(g), then W_o; dense masked scores, blocked over
  queries.
- FFN: a SwiGLU MLP, or the expert layer: `references/kimi_linear.routing`
  (float32 sigmoid scores over all experts, the top k of score + zeros,
  weights scaled * score / sum of the k selected), the shared expert plus
  a masked `lax.scan` over the experts held here, every token through each
  (a scan so that the 16 experts' weight gradients come back stacked and
  one expert's temporaries live at a time); what absent experts would add
  is left out.

Nothing of the program is imported.  Parameters are a nested dict under
the names the configuration's family lists; one layer of one sequence is
rematerialised at a time.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.references.kimi_linear import rms_norm, routing, swiglu
from benchmark.references.numerics import Numerics


def rotary(x, theta: float):
    """x [b, L, H, d]: (x1, x2) -> (x1 cos - x2 sin, x2 cos + x1 sin) with
    x1, x2 the head's two halves, angle_(t, i) = t theta^(-2 i / d)."""
    length, d = x.shape[1], x.shape[-1]
    half = d // 2
    inverse = theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / d)
    angle = jnp.arange(length, dtype=jnp.float32)[:, None] * inverse
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(nx: Numerics, x, p, sizes: dict, window, query_block: int = 256):
    """`window` None: a global layer."""
    b, length, _ = x.shape
    heads, kv, d = sizes["num_heads"], sizes["num_kv_heads"], sizes["head_dim"]

    def project(name, n):
        return nx.einsum("bld,df->blf", x, p[name]).reshape(b, length, n, d)

    q = rms_norm(project("q_kernel", heads), p["q_norm"], sizes["eps"])
    k = rms_norm(project("k_kernel", kv), p["k_norm"], sizes["eps"])
    v = project("v_kernel", kv)
    gate = jax.nn.sigmoid(nx.einsum("bld,df->blf", x, p["gate_kernel"]))
    if window is not None:
        q, k = rotary(q, sizes["rope_theta"]), rotary(k, sizes["rope_theta"])
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
        w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return nx.einsum("bhqk,bkhe->bqhe", w, v)

    starts = jnp.arange(0, length + pad, block)
    o = jax.lax.map(queries, (
        jnp.moveaxis(qp.reshape(b, -1, block, heads, d), 1, 0), starts))
    o = jnp.moveaxis(o, 0, 1).reshape(b, length + pad, -1)[:, :length]
    return nx.einsum("blf,fd->bld", o * gate, p["out_kernel"])


def moe(nx: Numerics, x, p, offset: int, top_k: int, scaling: float):
    tokens = x.reshape(-1, x.shape[-1])
    held = p["experts_gate_kernel"].shape[0]
    weights = routing(nx, tokens, p["router_kernel"], top_k, scaling)
    one = jax.checkpoint(lambda w, a, b, c: w[:, None]
                         * swiglu(nx, tokens, a, b, c))
    y, _ = jax.lax.scan(
        lambda y, xs: (y + one(*xs), None),
        swiglu(nx, tokens, p["shared_gate_kernel"], p["shared_up_kernel"],
               p["shared_down_kernel"]),
        (weights[:, offset:offset + held].T, p["experts_gate_kernel"],
         p["experts_up_kernel"], p["experts_down_kernel"]))
    return y.reshape(x.shape)


def block(nx: Numerics, h, p, mixer: str, ffn: str, sizes: dict):
    eps = sizes["eps"]
    y = attention(nx, rms_norm(h, p["mixer"]["norm"], eps),
                  p["mixer"]["core"], sizes,
                  sizes["window"] if mixer == "window" else None)
    a = h + rms_norm(y, p["mixer"]["post_norm"], eps)
    x = rms_norm(a, p["ffn"]["norm"], eps)
    f = p["ffn"]["core"]
    if ffn == "mlp":
        y = swiglu(nx, x.reshape(-1, x.shape[-1]), f["gate_kernel"],
                   f["up_kernel"], f["down_kernel"]).reshape(x.shape)
    else:
        y = moe(nx, x, f, sizes["expert_offset"], sizes["top_k"],
                sizes["routed_scaling"])
    return a + rms_norm(y, p["ffn"]["post_norm"], eps)


def features(params, tokens, sizes: dict, nx: Numerics):
    """One layer is rematerialised at a time, and within a layer one
    sequence at a time (`lax.map` over the batch), so that the check fits
    beside the weights, Adam's moments and the gradient."""
    h = sizes["embedding_scale"] * params["embedding"][
        tokens.astype(jnp.int32)]
    for i, (mixer, ffn) in enumerate(sizes["layers"]):
        p = params[f"layer{i + 1}"]
        one = jax.checkpoint(
            lambda row, p_, m=mixer, f=ffn:
            block(nx, row[None], p_, m, f, sizes)[0])
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
