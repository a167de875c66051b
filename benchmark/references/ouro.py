"""Plain forward pass and loss of the looped Ouro decoder, written from
its equations (ISSUE 48, PERF.md section 4; arXiv:2510.25741): a stack of
sandwich-norm blocks

    h += N2(Attn(N1(h)));  h += N4(MLP(N3(h)))

applied T = `loops` times to the same stream with the same parameters,

    h^0 = Emb(x);  h^t = N_f(Layers(h^(t-1))),  t = 1..T

the NORMED stream of a pass feeding the next, the untied head read after
every pass (z^t = h^t W_head), and the expected-exit loss

    a^t_i = h^t_i . w_g + b_g;  lambda^t = sigmoid(a^t)
    p^t = lambda^t prod_{j<t} (1 - lambda^j)  (t < T);  p^T the rest
    L = mean_i [ sum_t p^t_i ce^t_i - beta H(p_i) ],
    H(p_i) = - sum_t p^t_i log p^t_i

- Attn: q, k, v = x Wq, x Wk, x Wv, no biases, no q/k norm, no gate;
  rotary on every layer (rotate-half over the whole head, positions
  0..L-1, angle = position x theta^(-2 i / d)); query head n reads
  key/value head n // group; softmax(q k^T / sqrt(d)) v over every earlier
  key, then W_o; dense masked scores, blocked over queries (the rotary and
  the causal core are `references/glm4_moe_lite.py`'s, which are plain
  too).
- MLP: SwiGLU, no bias.

Nothing of the program is imported.  Parameters are a nested dict under
the names the configuration's family lists; a loop over the T steps calls
the same layer functions on the same tree (`streams` says why it is a
`lax.scan`), one layer application rematerialised at a time.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.references.glm4_moe_lite import causal_attention, rotary
from benchmark.references.kimi_linear import rms_norm, swiglu
from benchmark.references.numerics import Numerics


def attention(nx: Numerics, x, p, sizes: dict, query_block: int = 256):
    b, length, _ = x.shape
    heads, kv, d = sizes["num_heads"], sizes["num_kv_heads"], sizes["head_dim"]

    def project(name, n):
        return nx.einsum("bld,df->blf", x, p[name]).reshape(b, length, n, d)

    q = rotary(project("q_kernel", heads), sizes["rope_theta"])
    k = rotary(project("k_kernel", kv), sizes["rope_theta"])
    v = project("v_kernel", kv)
    if kv != heads:
        k, v = (jnp.repeat(y, heads // kv, axis=2) for y in (k, v))
    o = causal_attention(nx, q, k, v, query_block)
    return nx.einsum("blf,fd->bld", o.reshape(b, length, -1), p["out_kernel"])


def block(nx: Numerics, h, p, sizes: dict):
    """The sandwich: a norm before and a norm after each half."""
    eps = sizes["eps"]
    mixer, ffn = p["mixer"], p["ffn"]
    h = h + rms_norm(attention(nx, rms_norm(h, mixer["norm"], eps),
                               mixer["core"], sizes),
                     mixer["post_norm"], eps)
    core = ffn["core"]
    x = rms_norm(h, ffn["norm"], eps)
    y = swiglu(nx, x.reshape(-1, x.shape[-1]), core["gate_kernel"],
               core["up_kernel"], core["down_kernel"]).reshape(x.shape)
    return h + rms_norm(y, ffn["post_norm"], eps)


def streams(params, tokens, sizes: dict, nx: Numerics, stacks=None):
    """The T normed streams [T, b, L, hidden]: `lax.scan` over the loop
    steps, each calling the same layer functions on the same tree, one
    layer application rematerialised at a time.  A scan and not a Python
    loop so that the check fits: a parameter's gradient is then added up
    use by use inside the loop, where T separate uses leave XLA to fuse
    the T - 1 additions into one and keep every use's gradient (1.23 GB a
    use at the cell's size) until the last exists.  `stacks`: one tree of
    layers a loop step where the steps' copies are untied (the tests'
    sum-of-uses check); else every step runs `params`' own layers."""
    h = params["embedding"][tokens.astype(jnp.int32)]
    layer = jax.checkpoint(
        lambda row, p: block(nx, row[None], p, sizes)[0])

    def one_pass(h, tree):
        for i in range(len(sizes["layers"])):
            h = jax.lax.map(
                lambda row, p_=tree[f"layer{i + 1}"]: layer(row, p_), h)
        h = rms_norm(h, tree["final_norm"], sizes["eps"])
        return h, h

    if stacks is None:
        return jax.lax.scan(lambda h_, _: one_pass(h_, params), h, None,
                            length=sizes["loops"])[1]
    return jax.lax.scan(one_pass, h, jax.tree.map(
        lambda *copies: jnp.stack(copies), *stacks))[1]


def logits(params, tokens, sizes: dict, nx: Numerics):
    """The last step's."""
    return nx.einsum("bld,dv->blv", streams(params, tokens, sizes, nx)[-1],
                     params["head_kernel"])


def exit_masses(a, last_takes_rest: bool = True):
    """log p [T, N] of the gate's logits a [T, N]: log lambda^t + sum_{j<t}
    log(1 - lambda^j), the last step the rest of the mass (or, planted
    fault, lambda^T's share of it, so that a token's masses fall short of
    1)."""
    log_go, log_stay = jax.nn.log_sigmoid(a), jax.nn.log_sigmoid(-a)
    out, stayed = [], jnp.zeros_like(a[0])
    for t in range(a.shape[0]):
        last = t == a.shape[0] - 1
        out.append(stayed if last and last_takes_rest
                   else log_go[t] + stayed)
        stayed = stayed + log_stay[t]
    return jnp.stack(out)


def token_losses(nx: Numerics, h, head, labels, token_block: int):
    """Cross-entropy of every row of h [R, d], the logits a block of rows
    at a time."""
    rows = h.shape[0]
    step = min(token_block, rows)
    pad = (-rows) % step
    h = jnp.pad(h, ((0, pad), (0, 0)))
    y = jnp.pad(labels, (0, pad))

    @jax.checkpoint
    def some(xs):
        h_, y_ = xs
        z = nx.einsum("td,dv->tv", h_, head)
        return jax.nn.logsumexp(z, axis=-1) - jnp.take_along_axis(
            z, y_[:, None], -1)[:, 0]

    each = jax.lax.map(some, (h.reshape(-1, step, h.shape[-1]),
                              y.reshape(-1, step)))
    return each.reshape(-1)[:rows]


def losses(params, tokens, labels, sizes: dict, nx: Numerics,
           token_block: int = 2048, last_takes_rest: bool = True,
           stacks=None):
    """(total, each step's mean cross-entropy [T], each step's mean exit
    mass [T], the mean entropy): the total is what the program's step
    reports, the rest what its counters do."""
    loops = sizes["loops"]
    h = streams(params, tokens, sizes, nx, stacks)
    h = h.reshape(loops, -1, h.shape[-1])
    y = labels.reshape(-1).astype(jnp.int32)
    gate = params["exit_gate"]
    a = nx.einsum("tnd,d->tn", h, gate["kernel"][:, 0]) + gate["bias"][0]
    log_p = exit_masses(a, last_takes_rest)
    p = jnp.exp(log_p)
    ce = token_losses(nx, h.reshape(-1, h.shape[-1]), params["head_kernel"],
                      jnp.tile(y, loops), token_block).reshape(loops, -1)
    entropy = jnp.mean(-jnp.sum(p * log_p, axis=0))
    total = jnp.mean(jnp.sum(p * ce, axis=0)) - sizes["exit_beta"] * entropy
    return total, jnp.mean(ce, axis=1), jnp.mean(p, axis=1), entropy


def loss(params, tokens, labels, sizes: dict, nx: Numerics, **how):
    return losses(params, tokens, labels, sizes, nx, **how)[0]
