"""Plain forward pass and loss of the Kimi-Linear decoder, written from
its equations (ISSUE 27, PERF.md section 4): pre-RMSNorm residual blocks,
`h += Mixer(RMSNorm(h)); h += FFN(RMSNorm(h))`, a final RMSNorm, an
untied head, mean next-token cross-entropy in float32.  No rotary
anywhere (`mla_use_nope`).

- KDA: q, k, v = SiLU(ShortConv4(W x)); q and k L2-normalised per head, q
  scaled by d_k^-1/2; per-channel log-decay g_t = -exp(A_log_h) *
  softplus(W_up W_down x_t + dt_bias), beta_t = sigmoid(w_beta_h . x_t);
  **the token-by-token recurrence** S_t = (I - beta_t k_t k_t^T)
  Diag(exp g_t) S_(t-1) + beta_t k_t v_t^T, o_t = S_t^T q_t, as two nested
  `lax.scan`s, the outer one checkpointed, so that the backward pass holds
  one state a block and one block's steps, not 8,192 states; output
  W_o [RMSNorm_head(o_t) * sigmoid(W_up W_down x_t)].
- MLA: 192-wide q and k (128 per head from the latent, 64 shared by all
  heads), 128-wide v, causal softmax(q k^T / sqrt(192)) v, the scores
  blocked over queries.
- MoE: float32 sigmoid scores over all experts, the top k of score +
  bias (zeros), weights scaled * score / sum of the k selected; the shared
  expert plus, as a masked loop over the experts held here, w_e
  SwiGLU_e(x).  What absent experts would add is left out.

Nothing of the program is imported.  Parameters are a nested dict under
the names the configuration's family lists; one layer of one sequence is
rematerialised at a time.  `A_LOG_CENTRE` and `DT_BIAS_CENTRE` are the configuration's
assumed centres of the decay gate's initialisation (its file says why).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.references.numerics import Numerics

A_LOG_CENTRE = 1.96
DT_BIAS_CENTRE = -4.6


def rms_norm(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * p["scale"]


def silu(x):
    return x * jax.nn.sigmoid(x)


def short_conv(x, kernel):
    """x [b, L, C], kernel [taps, C]: y_t = sum_j kernel[j] x_(t - (taps -
    1 - j)), zeros before the sequence's start."""
    taps, length = kernel.shape[0], x.shape[1]
    y = jnp.zeros_like(x)
    for j in range(taps):
        back = taps - 1 - j
        shifted = jnp.concatenate(
            [jnp.zeros_like(x[:, :back]), x[:, :length - back]], axis=1)
        y = y + shifted * kernel[j]
    return y


def delta_rule_recurrence(nx: Numerics, q, k, v, g, beta, block: int = 64):
    """q, k, g [b, L, H, dk], v [b, L, H, dv], beta [b, L, H] -> o
    [b, L, H, dv], a token at a time from a zero state."""
    b, length, heads, dk = q.shape
    pad = (-length) % block
    if pad:     # tokens that neither write nor decay, cut off again below
        q, k, v, g, beta = (jnp.pad(
            x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta))

    def token(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        state = jnp.exp(g_t)[..., None] * state
        seen = nx.einsum("bhd,bhde->bhe", k_t, state)
        state = state + (b_t[..., None, None] * k_t[..., :, None]
                         * (v_t - seen)[..., None, :])
        return state, nx.einsum("bhd,bhde->bhe", q_t, state)

    @jax.checkpoint
    def tokens(state, xs):
        return jax.lax.scan(token, state, xs)

    def blocks(x):          # [b, L, ...] -> [L / block, block, b, ...]
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape((-1, block) + x.shape[1:])

    state = jnp.zeros((b, heads, dk, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(tokens, state, tuple(map(blocks, (q, k, v, g, beta))))
    o = jnp.moveaxis(o.reshape((-1,) + o.shape[2:]), 0, 1)
    return o[:, :length]


def kda(nx: Numerics, x, p, heads: int, eps: float):
    b, length, _ = x.shape

    def branch(name):
        y = nx.einsum("bld,df->blf", x, p[name + "_kernel"])
        y = silu(short_conv(y, p[name + "_conv"]))
        return y.reshape(b, length, heads, -1)

    def low_rank(name):
        return nx.einsum("blr,rf->blf",
                         nx.einsum("bld,dr->blr", x, p[name + "_down"]),
                         p[name + "_up"])

    def unit(y):
        return y * jax.lax.rsqrt(jnp.sum(y * y, -1, keepdims=True) + 1e-6)

    q, k, v = branch("q"), branch("k"), branch("v")
    dk = q.shape[-1]
    q, k = unit(q) / math.sqrt(dk), unit(k)
    rate = jnp.exp(A_LOG_CENTRE + p["A_log"])[:, None]
    g = -rate * jax.nn.softplus(
        (low_rank("f") + p["dt_bias"] + DT_BIAS_CENTRE)
        .reshape(b, length, heads, dk))
    beta = jax.nn.sigmoid(nx.einsum("bld,dh->blh", x, p["beta_kernel"]))
    o = delta_rule_recurrence(nx, q, k, v, g, beta)
    gate = jax.nn.sigmoid(low_rank("g")).reshape(o.shape)
    o = rms_norm(o, p["out_norm"], eps) * gate
    return nx.einsum("blf,fd->bld", o.reshape(b, length, -1), p["out_kernel"])


def mla(nx: Numerics, x, p, heads: int, nope: int, rope: int, rank: int,
        eps: float, query_block: int = 256):
    b, length, _ = x.shape
    q = nx.einsum("bld,df->blf", x, p["q_kernel"]).reshape(
        b, length, heads, nope + rope)
    kv = nx.einsum("bld,df->blf", x, p["kv_a_kernel"])
    latent, shared = rms_norm(kv[..., :rank], p["kv_norm"], eps), \
        kv[..., rank:]
    kv = nx.einsum("blr,rf->blf", latent, p["kv_b_kernel"]).reshape(
        b, length, heads, -1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        shared[:, :, None, :], (b, length, heads, rope))], -1)
    v = kv[..., nope:]
    block = min(query_block, length)
    pad = (-length) % block
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    key_at = jnp.arange(length)

    @jax.checkpoint
    def queries(args):
        q_, start = args
        s = nx.einsum("bqhe,bkhe->bhqk", q_, k) / math.sqrt(nope + rope)
        seen = key_at[None, :] <= (start + jnp.arange(block))[:, None]
        w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return nx.einsum("bhqk,bkhe->bqhe", w, v)

    starts = jnp.arange(0, length + pad, block)
    o = jax.lax.map(queries, (
        jnp.moveaxis(qp.reshape(b, -1, block, heads, nope + rope), 1, 0),
        starts))
    o = jnp.moveaxis(o, 0, 1).reshape(b, length + pad, -1)[:, :length]
    return nx.einsum("blf,fd->bld", o, p["out_kernel"])


def swiglu(nx: Numerics, x, gate, up, down):
    return nx.einsum("tf,fd->td", silu(nx.einsum("td,df->tf", x, gate))
                     * nx.einsum("td,df->tf", x, up), down)


def routing(nx: Numerics, x, router, top_k: int, scaling: float):
    """x [T, d] -> [T, num_experts]: each token's weight for every expert,
    zero where it was not selected."""
    scores = jax.nn.sigmoid(nx.einsum("td,de->te", x, router))
    bias = jnp.zeros((router.shape[-1],), jnp.float32)
    _, chosen = jax.lax.top_k(scores + bias, top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = scaling * picked / jnp.sum(picked, -1, keepdims=True)
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, chosen].set(weights)


def moe(nx: Numerics, x, p, offset: int, top_k: int, scaling: float):
    tokens = x.reshape(-1, x.shape[-1])
    weights = routing(nx, tokens, p["router_kernel"], top_k, scaling)
    y = swiglu(nx, tokens, p["shared_gate_kernel"], p["shared_up_kernel"],
               p["shared_down_kernel"])
    one = jax.checkpoint(lambda t, w, a, b, c: w[:, None]
                         * swiglu(nx, t, a, b, c))
    for e in range(p["experts_gate_kernel"].shape[0]):
        y = y + one(tokens, weights[:, offset + e],
                    p["experts_gate_kernel"][e], p["experts_up_kernel"][e],
                    p["experts_down_kernel"][e])
    return y.reshape(x.shape)


def block(nx: Numerics, h, p, mixer: str, ffn: str, sizes: dict):
    eps = sizes["eps"]
    x = rms_norm(h, p["mixer"]["norm"], eps)
    if mixer == "kda":
        h = h + kda(nx, x, p["mixer"]["core"], sizes["num_heads"], eps)
    else:
        h = h + mla(nx, x, p["mixer"]["core"], sizes["num_heads"],
                    sizes["qk_nope_dim"], sizes["qk_rope_dim"],
                    sizes["kv_rank"], eps)
    x = rms_norm(h, p["ffn"]["norm"], eps)
    f = p["ffn"]["core"]
    if ffn == "mlp":
        return h + swiglu(nx, x.reshape(-1, x.shape[-1]), f["gate_kernel"],
                          f["up_kernel"], f["down_kernel"]).reshape(x.shape)
    return h + moe(nx, x, f, sizes["expert_offset"], sizes["top_k"],
                   sizes["routed_scaling"])


def features(params, tokens, sizes: dict, nx: Numerics):
    """One layer is rematerialised at a time, and within a layer one
    sequence at a time (`lax.map` over the batch): at 8,192 tokens a
    layer's float32 temporaries are gigabytes a sequence, and the check
    has to fit beside the weights, Adam's moments and the gradient."""
    h = params["embedding"][tokens.astype(jnp.int32)]
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
