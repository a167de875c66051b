"""Plain forward pass and loss of the GLM-4-MoE-Lite decoder
(GLM-4.7-Flash), written from its equations (ISSUE 45, PERF.md section 4):
pre-norm blocks,

    h += Attn(N1(h));  h += FFN(N2(h))

a final RMSNorm, an untied head, and one multi-token-prediction module
(or a chain of them) that shares embedding and head; the loss is the mean
next-token cross-entropy plus `mtp_weight` times the mean over depths of
the modules' cross-entropies, in float32.

- Attn: c_q = N_q(x W_qa); q = c_q W_qb, heads of [nope | rope];
  [c_kv | k_r] = x W_kva; [k_nope | v] = N_kv(c_kv) W_kvb per head.
  Rotary over the rope part, rotate-half pairing (i, i + rope / 2),
  w_i = theta^(-2 i / rope), positions 0..L-1, no scaling: on each head's
  q_rope, and on k_r, which every head then reads.  k = [k_nope | k_r];
  softmax(q k^T / sqrt(nope + rope)) v, causal, dense masked scores
  blocked over queries, a few heads at a time; then W_o.
- FFN: a SwiGLU MLP in the leading dense layers (a block of tokens at a
  time); else s = sigmoid(x W_r) in
  float32 over ALL the experts, the `top_k` largest of s + b (b zeros),
  w = scaling s / sum of the picked; the shared SwiGLU expert plus, as a
  masked loop over the experts held here, w_e SwiGLU_e(x).  What absent
  experts would add is left out.
- MTP, depth k = 1..D (arXiv:2412.19437, eq. 21-25), literally over the
  L - k positions that have a label: with h^0 the stream behind the last
  block BEFORE the final norm and t_(i+1) = labels[i],
      h'^k_i = [N_h(h^(k-1)_i) ; N_e(Emb(t_(i+k)))] W_eh^k
      h^k    = Block^k(h'^k)          (causal over those L - k positions)
      loss^k = mean_i CE(N_out^k(h^k_i) W_head, t_(i+k+1))
  and loss = loss_main + mtp_weight / D x sum_k loss^k.

Nothing of the program is imported.  Parameters are a nested dict under
the names the configuration's family lists; one layer of one sequence is
rematerialised at a time.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.references.kimi_linear import rms_norm, routing, swiglu
from benchmark.references.numerics import Numerics


def rotary(x, theta: float):
    """x [b, L, ..., d]: (x1, x2) -> (x1 cos - x2 sin, x2 cos + x1 sin)
    with x1, x2 the last axis' two halves, angle = position x
    theta^(-2 i / d), i = 0..d/2-1."""
    length, half = x.shape[1], x.shape[-1] // 2
    w = theta ** (-2.0 * np.arange(half, dtype=np.float64) / x.shape[-1])
    angle = jnp.arange(length, dtype=jnp.float32)[:, None] \
        * jnp.asarray(w, jnp.float32)
    angle = angle.reshape((1, length) + (1,) * (x.ndim - 3) + (half,))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def causal_attention(nx: Numerics, q, k, v, query_block: int):
    """softmax(q k^T / sqrt(d), causal) v for q, k [b, L, h, d] and v
    [b, L, h, e]: dense masked scores, a block of queries at a time."""
    b, length, heads, d = q.shape
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
    return jnp.moveaxis(o, 0, 1).reshape(
        b, length + pad, heads, -1)[:, :length]


def attention(nx: Numerics, x, p, sizes: dict, query_block: int = 256,
              head_block: int = 4):
    """The heads `head_block` at a time, each group rematerialised: its q,
    k, v and output are [L, group, 256] float32 (0.34 GB each at 16,384
    tokens and 20 heads whole), and the check has to fit beside the
    weights, Adam's moments and the gradient.  The latents and the shared
    key part are made once and read by every group."""
    b, length, _ = x.shape
    heads, nope, rope = sizes["num_heads"], sizes["qk_nope_dim"], \
        sizes["qk_rope_dim"]
    rank, eps, theta = sizes["kv_rank"], sizes["eps"], sizes["rope_theta"]
    c_q = rms_norm(nx.einsum("bld,dr->blr", x, p["q_a_kernel"]),
                   p["q_norm"], eps)
    kv = nx.einsum("bld,df->blf", x, p["kv_a_kernel"])
    latent = rms_norm(kv[..., :rank], p["kv_norm"], eps)
    k_r = rotary(kv[..., rank:], theta)                    # once, all heads
    per = head_block if heads % head_block == 0 else heads

    def grouped(w, rows):
        """[rows, heads x e] -> [groups, rows, per x e]."""
        return jnp.moveaxis(w.reshape(rows, heads // per, -1), 1, 0)

    @jax.checkpoint
    def group(w_q, w_kv, w_o):
        q = nx.einsum("blr,rf->blf", c_q, w_q).reshape(
            b, length, per, nope + rope)
        q = jnp.concatenate([q[..., :nope], rotary(q[..., nope:], theta)], -1)
        kv_ = nx.einsum("blr,rf->blf", latent, w_kv).reshape(
            b, length, per, -1)
        k = jnp.concatenate([kv_[..., :nope], jnp.broadcast_to(
            k_r[:, :, None, :], (b, length, per, rope))], -1)
        o = causal_attention(nx, q, k, kv_[..., nope:], query_block)
        return nx.einsum("blf,fd->bld", o.reshape(b, length, -1), w_o)

    out = p["out_kernel"]
    y, _ = jax.lax.scan(
        lambda y, ws: (y + group(*ws), None), jnp.zeros_like(x),
        (grouped(p["q_b_kernel"], c_q.shape[-1]),
         grouped(p["kv_b_kernel"], rank),
         out.reshape(heads // per, -1, out.shape[-1])))
    return y


def moe(nx: Numerics, x, p, offset: int, top_k: int, scaling: float):
    """The shared expert plus the held experts' weighted parts, every token
    through each held expert under its weight (zero where not picked)."""
    tokens = x.reshape(-1, x.shape[-1])
    held = p["experts_gate_kernel"].shape[0]
    weights = routing(nx, tokens, p["router_kernel"], top_k, scaling)
    y = swiglu(nx, tokens, p["shared_gate_kernel"], p["shared_up_kernel"],
               p["shared_down_kernel"])
    one = jax.checkpoint(lambda w, a, b, c: w[:, None]
                         * swiglu(nx, tokens, a, b, c))
    y, _ = jax.lax.scan(
        lambda y, xs: (y + one(*xs), None), y,
        (weights[:, offset:offset + held].T, p["experts_gate_kernel"],
         p["experts_up_kernel"], p["experts_down_kernel"]))
    return y.reshape(x.shape)


def dense_mlp(nx: Numerics, x, p, token_block: int = 2048):
    """The SwiGLU MLP a block of tokens at a time, each rematerialised: at
    16,384 tokens of a 10,240-wide layer the float32 gate, up and their
    cotangents are 0.67 GB apiece whole, and the check has to fit beside
    the weights, Adam's moments and the gradient."""
    tokens = x.reshape(-1, x.shape[-1])
    total = tokens.shape[0]
    step = min(token_block, total)
    pad = (-total) % step
    one = jax.checkpoint(lambda t: swiglu(
        nx, t, p["gate_kernel"], p["up_kernel"], p["down_kernel"]))
    y = jax.lax.map(one, jnp.pad(tokens, ((0, pad), (0, 0))).reshape(
        -1, step, tokens.shape[-1]))
    return y.reshape(-1, tokens.shape[-1])[:total].reshape(x.shape)


def block(nx: Numerics, h, p, ffn: str, sizes: dict):
    eps = sizes["eps"]
    h = h + attention(nx, rms_norm(h, p["mixer"]["norm"], eps),
                      p["mixer"]["core"], sizes)
    x = rms_norm(h, p["ffn"]["norm"], eps)
    f = p["ffn"]["core"]
    if ffn == "mlp":
        return h + dense_mlp(nx, x, f)
    return h + moe(nx, x, f, sizes["expert_offset"], sizes["top_k"],
                   sizes["routed_scaling"])


def per_sequence(nx: Numerics, h, p, ffn: str, sizes: dict):
    """One block over [b, L, d], a sequence at a time, rematerialised."""
    one = jax.checkpoint(
        lambda row, p_: block(nx, row[None], p_, ffn, sizes)[0])
    return jax.lax.map(lambda row: one(row, p), h)


def stream(params, tokens, sizes: dict, nx: Numerics):
    """The residual stream behind the last block, before the final norm."""
    h = params["embedding"][tokens.astype(jnp.int32)]
    for i, (_, ffn) in enumerate(sizes["layers"]):       # every mixer "mla"
        h = per_sequence(nx, h, params[f"layer{i + 1}"], ffn, sizes)
    return h


def features(params, tokens, sizes: dict, nx: Numerics):
    return rms_norm(stream(params, tokens, sizes, nx), params["final_norm"],
                    sizes["eps"])


def logits(params, tokens, sizes: dict, nx: Numerics):
    return nx.einsum("bld,dv->blv", features(params, tokens, sizes, nx),
                     params["head_kernel"])


def cross_entropy(nx: Numerics, h, head, labels, token_block: int):
    """Mean cross-entropy of `labels` [T] under h [T, d] through `head`,
    the logits a block of tokens at a time."""
    total = h.shape[0]
    step = min(token_block, total)
    pad = (-total) % step
    h = jnp.pad(h, ((0, pad), (0, 0)))
    y = jnp.pad(labels.astype(jnp.int32), (0, pad), constant_values=-1)

    @jax.checkpoint
    def some(carry, xs):
        h_, y_ = xs
        z = nx.einsum("td,dv->tv", h_, head)
        picked = jnp.take_along_axis(z, jnp.maximum(y_, 0)[:, None], -1)[:, 0]
        each = jax.nn.logsumexp(z, axis=-1) - picked
        return carry + jnp.sum(jnp.where(y_ >= 0, each, 0.0)), None

    summed, _ = jax.lax.scan(some, jnp.zeros((), jnp.float32),
                             (h.reshape(-1, step, h.shape[-1]),
                              y.reshape(-1, step)))
    return summed / total


def losses(params, tokens, labels, sizes: dict, nx: Numerics,
           token_block: int = 2048):
    """(main loss, [each depth's loss])."""
    eps, head = sizes["eps"], params["head_kernel"]
    h = stream(params, tokens, sizes, nx)
    flat = lambda a: a.reshape((-1,) + a.shape[2:])
    main = cross_entropy(nx, flat(rms_norm(h, params["final_norm"], eps)),
                         head, flat(labels), token_block)
    further = []
    length = labels.shape[1]
    for k in range(1, sizes.get("mtp_depth", 0) + 1):
        p = params[f"mtp{k}"]
        keep = length - k           # positions 0..L-k-1 have a token i+k+1
        known = params["embedding"][labels[:, k - 1:k - 1 + keep]
                                    .astype(jnp.int32)]
        joined = jnp.concatenate(
            [rms_norm(h[:, :keep], p["hidden_norm"], eps),
             rms_norm(known, p["token_norm"], eps)], -1)
        h = per_sequence(nx, nx.einsum("blf,fd->bld", joined,
                                       p["join_kernel"]),
                         p["block"], sizes["mtp_block"][1], sizes)
        further.append(cross_entropy(
            nx, flat(rms_norm(h, p["out_norm"], eps)), head,
            flat(labels[:, k:]), token_block))
    return main, further


def loss(params, tokens, labels, sizes: dict, nx: Numerics,
         token_block: int = 2048):
    """What the program's step reports: main + mtp_weight x the depths'
    mean."""
    main, further = losses(params, tokens, labels, sizes, nx, token_block)
    if not further:
        return main
    return main + sizes["mtp_weight"] * sum(further) / len(further)
