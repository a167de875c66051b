"""Plain forward pass and loss of the Nemotron-H decoder (Nemotron-3-Super),
written from its equations (ISSUE 38, PERF.md section 4): every layer is
one half, `h += F(RMSNorm(h))` with F a Mamba-2 mixer ("mamba"), plain
grouped-query attention ("attention") or a LatentMoE ("moe"); a final
RMSNorm, an untied head, mean next-token cross-entropy in float32.  No
bias but the convolution's, no positions anywhere.

- Mamba-2, for the heads and groups given: [z, xBC, dt] = x W_in; xBC =
  SiLU(ShortConv4(xBC) + bias), split into X [H, P], B, C [G, N]; dt =
  softplus(dt + dt_bias); **the token-by-token recurrence** S_t =
  exp(-exp(A_log) dt_t) S_(t-1) + dt_t X_t B_t^T, Y_t = S_t C_t + D X_t
  (B and C of the head's group), as two nested `lax.scan`s, the outer one
  checkpointed, so that the backward pass holds one state a block and one
  block's steps, not 8,192 states; no chunking; output W_out
  [GroupRMSNorm(Y * SiLU(z))], the norm over each group's channels.
- Attention: q, k, v = x Wq, x Wk, x Wv; query head n reads key/value
  head n // group (k and v repeated here); causal softmax(q k^T /
  sqrt(d)) v, dense masked scores blocked over queries; W_o.
- LatentMoE: `references/kimi_linear.routing` (float32 sigmoid scores
  over all experts, the top k of score + zeros, weights scaled * score /
  sum of the k selected); u = x W_dn; r = sum over the experts held here
  of w_e relu(u W1_e)^2 W2_e, a loop over them, every token through each;
  y = r W_up + relu(x S1)^2 S2.  What absent experts would add is left
  out.

Nothing of the program is imported.  Parameters are a nested dict under
the names the configuration's family lists; one layer of one sequence is
rematerialised at a time.  `A_LOG_CENTRE`, `DT_BIAS_CENTRE` and `D_CENTRE`
are the configuration's assumed centres of upstream's initialisation (its
file says why).  The same functions with every head, group and expert
are the uncut layer (`tests/test_nemotron_h.py`: the shares add up).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.references.kimi_linear import (rms_norm, routing, short_conv,
                                              silu)
from benchmark.references.numerics import Numerics

A_LOG_CENTRE = 1.96
DT_BIAS_CENTRE = -4.6
D_CENTRE = 1.0


def state_space_recurrence(nx: Numerics, x, dt, a, b, c, block: int = 64):
    """x [b, L, H, P], dt [b, L, H], a [H] (< 0), b, c [b, L, G, N] -> y
    [b, L, H, P], a token at a time from a zero state."""
    bsz, length, heads, p = x.shape
    per = heads // b.shape[2]
    pad = (-length) % block
    if pad:     # tokens that neither write nor decay, cut off again below
        x, dt, b, c = (jnp.pad(
            y, ((0, 0), (0, pad)) + ((0, 0),) * (y.ndim - 2))
            for y in (x, dt, b, c))

    def token(state, xs):
        x_t, dt_t, b_t, c_t = xs
        b_h, c_h = (jnp.repeat(y, per, axis=1) for y in (b_t, c_t))
        state = (jnp.exp(a * dt_t)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., :, None] * b_h[..., None, :])
        return state, nx.einsum("bhpn,bhn->bhp", state, c_h)

    @jax.checkpoint
    def tokens(state, xs):
        return jax.lax.scan(token, state, xs)

    def blocks(y):          # [b, L, ...] -> [L / block, block, b, ...]
        y = jnp.moveaxis(y, 1, 0)
        return y.reshape((-1, block) + y.shape[1:])

    state = jnp.zeros((bsz, heads, p, b.shape[-1]), jnp.float32)
    _, y = jax.lax.scan(tokens, state, tuple(map(blocks, (x, dt, b, c))))
    y = jnp.moveaxis(y.reshape((-1,) + y.shape[2:]), 0, 1)
    return y[:, :length]


def mamba2(nx: Numerics, x, p, sizes: dict):
    bsz, length, _ = x.shape
    heads, head, groups, state = (sizes["mamba_heads"],
                                  sizes["mamba_head_dim"],
                                  sizes["mamba_groups"], sizes["state_size"])
    inner, bc = heads * head, groups * state
    mixed = nx.einsum("bld,df->blf", x, p["in_kernel"])
    z, xbc, dt = (mixed[..., :inner], mixed[..., inner:2 * inner + 2 * bc],
                  mixed[..., 2 * inner + 2 * bc:])
    xbc = silu(short_conv(xbc, p["conv_kernel"]) + p["conv_bias"])
    xs = xbc[..., :inner].reshape(bsz, length, heads, head)
    b = xbc[..., inner:inner + bc].reshape(bsz, length, groups, state)
    c = xbc[..., inner + bc:].reshape(bsz, length, groups, state)
    dt = jax.nn.softplus(dt + DT_BIAS_CENTRE + p["dt_bias"])
    a = -jnp.exp(A_LOG_CENTRE + p["A_log"])
    y = state_space_recurrence(nx, xs, dt, a, b, c)
    y = y + (D_CENTRE + p["D"])[:, None] * xs
    y = (y.reshape(bsz, length, groups, -1)
         * silu(z).reshape(bsz, length, groups, -1))
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + sizes["eps"])
    y = y.reshape(bsz, length, inner) * p["out_norm"]["scale"]
    return nx.einsum("blf,fd->bld", y, p["out_kernel"])


def attention(nx: Numerics, x, p, sizes: dict, query_block: int = 256):
    b, length, _ = x.shape
    heads, kv, d = sizes["num_heads"], sizes["num_kv_heads"], sizes["head_dim"]

    def project(name, n):
        return nx.einsum("bld,df->blf", x, p[name]).reshape(b, length, n, d)

    q = project("q_kernel", heads)
    k, v = (jnp.repeat(project(name, kv), heads // kv, axis=2)
            for name in ("k_kernel", "v_kernel"))
    block = min(query_block, length)
    pad = (-length) % block
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    key_at = jnp.arange(length)

    @jax.checkpoint
    def queries(args):
        q_, start = args
        s = nx.einsum("bqhe,bkhe->bhqk", q_, k) / math.sqrt(d)
        seen = key_at[None, :] <= (start + jnp.arange(block))[:, None]
        w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return nx.einsum("bhqk,bkhe->bqhe", w, v)

    starts = jnp.arange(0, length + pad, block)
    o = jax.lax.map(queries, (
        jnp.moveaxis(qp.reshape(b, -1, block, heads, d), 1, 0), starts))
    o = jnp.moveaxis(o, 0, 1).reshape(b, length + pad, -1)[:, :length]
    return nx.einsum("blf,fd->bld", o, p["out_kernel"])


def relu2(nx: Numerics, x, up, down):
    return nx.einsum("tf,fd->td", jnp.square(jax.nn.relu(
        nx.einsum("td,df->tf", x, up))), down)


def routed(nx: Numerics, x, p, sizes: dict):
    """The held experts' part of a LatentMoE's result, through W_up."""
    tokens = x.reshape(-1, x.shape[-1])
    weights = routing(nx, tokens, p["router_kernel"], sizes["top_k"],
                      sizes["routed_scaling"])
    u = nx.einsum("td,dr->tr", tokens, p["latent_down_kernel"])
    one = jax.checkpoint(lambda w, a, b: w[:, None] * relu2(nx, u, a, b))
    r = jnp.zeros_like(u)
    for e in range(p["experts_up_kernel"].shape[0]):
        r = r + one(weights[:, sizes["expert_offset"] + e],
                    p["experts_up_kernel"][e], p["experts_down_kernel"][e])
    return nx.einsum("tr,rd->td", r, p["latent_up_kernel"]).reshape(x.shape)


def shared(nx: Numerics, x, p):
    return relu2(nx, x.reshape(-1, x.shape[-1]), p["shared_up_kernel"],
                 p["shared_down_kernel"]).reshape(x.shape)


def latent_moe(nx: Numerics, x, p, sizes: dict):
    return routed(nx, x, p, sizes) + shared(nx, x, p)


def block(nx: Numerics, h, p, mixer, ffn, sizes: dict):
    """One layer: the one half it has."""
    half = p["mixer" if ffn is None else "ffn"]
    x = rms_norm(h, half["norm"], sizes["eps"])
    if ffn is not None:
        return h + latent_moe(nx, x, half["core"], sizes)
    if mixer == "mamba":
        return h + mamba2(nx, x, half["core"], sizes)
    return h + attention(nx, x, half["core"], sizes)


def features(params, tokens, sizes: dict, nx: Numerics):
    """One layer is rematerialised at a time, and within a layer one
    sequence at a time (`lax.map` over the batch), so that the check fits
    beside the weights, Adam's moments and the gradient."""
    h = params["embedding"][tokens.astype(jnp.int32)]
    for i, (mixer, ffn) in enumerate(sizes["layers"]):
        p = params[f"layer{i + 1}"]
        one = jax.checkpoint(
            lambda row, p_, m=mixer, f=ffn:
            block(nx, row[None], p_, m, f, sizes)[0])
        h = jax.lax.map(lambda row, p_=p, one_=one: one_(row, p_), h)
    return rms_norm(h, params["final_norm"], sizes["eps"])


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
