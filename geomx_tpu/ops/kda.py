"""Kimi Delta Attention: the gated delta rule with a per-channel decay, as
a chunkwise-parallel algorithm.

Per head, with a state ``S`` in R^(dk x dv)::

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_(t-1) + beta_t k_t v_t^T
    o_t = S_t^T q_t

``g_t <= 0`` is the log-decay of each of the dk key channels.  A
token-by-token scan of this is 8,192 dependent steps a sequence; here a
chunk of ``chunk`` tokens is folded into matrix products (the WY form of
the delta rule: within a chunk the pseudo-values ``U = T (V - Kbar S_0)``
with ``T = (I + Diag(beta) A_kk)^-1 Diag(beta)``), and only the state's
hand-over from chunk to chunk is sequential (`lax.scan`, one step a
chunk, four products a step).

Decay lives in float32 log space and only differences ``G_i - G_j <= 0``
of the cumulative log-decay are ever exponentiated: a cumulative
product's reciprocal overflows at exp(-5) a token within one chunk.  The
score matrices ``A[i, j] = sum_d x_id k_jd exp(G_id - G_jd)`` are
therefore built from sub-blocks of ``sub`` tokens: a block below the
diagonal factors through the log-decay at its rows' block boundary (both
factors <= 0, one matrix product), a block on the diagonal is summed
channel by channel under its mask.

The backward pass is JAX's own, through the scan; callers rematerialise
(`jax.checkpoint`) the layer that holds the call.  This is the jnp form:
the only path off a TPU and the oracle of the Pallas kernel pair in
`ops/kda_pallas.py`, which a TPU runs (`ops/dispatch.kda` decides;
PERF.md, PR 31, says what plain XLA cost).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from geomx_tpu.utils.profiler import profile_scope

_HIGHEST = lax.Precision.HIGHEST
# the most elements a diagonal pass may hold at once ([.., sub, sub, dk]
# float32): the chunks are walked in groups of this size
_DIAG_ELEMENTS = 1 << 24


def unit_lower_inverse(m: jax.Array) -> jax.Array:
    """(I + m)^-1 for strictly lower-triangular ``m`` [..., n, n], n a
    power of two, in float32 at HIGHEST.  Blocks of 16 by the finite
    Neumann product (I - m)(I + m^2)(I + m^4)(I + m^8); larger ones by
    the 2x2 block formula."""
    n = m.shape[-1]
    mm = functools.partial(jnp.matmul, precision=_HIGHEST)
    if n <= 16:
        eye = jnp.eye(n, dtype=m.dtype)
        out, power, reach = eye - m, mm(m, m), 2
        while reach < n:
            out = mm(out, eye + power)
            power, reach = mm(power, power), 2 * reach
        return out
    h = n // 2
    a = unit_lower_inverse(m[..., :h, :h])
    d = unit_lower_inverse(m[..., h:, h:])
    low = -mm(mm(d, m[..., h:, :h]), a)
    top = jnp.concatenate([a, jnp.zeros_like(low).swapaxes(-1, -2)], -1)
    return jnp.concatenate([top, jnp.concatenate([low, d], -1)], -2)


def _diagonal_blocks(q, k, gs):
    """[..., ns, s, d] -> (qk, kk) [..., ns, s, s]: within each sub-block,
    sum_d x_id k_jd exp(G_id - G_jd) for j <= i (qk) and j < i (kk)."""
    s = q.shape[-2]
    row = lax.broadcasted_iota(jnp.int32, (s, s), 0)
    col = lax.broadcasted_iota(jnp.int32, (s, s), 1)

    def one(args):
        q_, k_, g_ = args
        diff = g_[..., :, None, :] - g_[..., None, :, :]
        decay = jnp.exp(jnp.where((col <= row)[..., None], diff, -jnp.inf))
        kd = k_[..., None, :, :] * decay
        qk = jnp.sum(q_[..., :, None, :] * kd, -1)
        kk = jnp.sum(k_[..., :, None, :] * kd, -1)
        return qk, jnp.where(col < row, kk, 0.0)

    n = q.shape[0]
    per_chunk = q[0].size * s
    groups = max(1, min(n, -(-n * per_chunk // _DIAG_ELEMENTS)))
    while n % groups:
        groups += 1
    if groups == 1:
        return one((q, k, gs))
    split = lambda x: x.reshape((groups, n // groups) + x.shape[1:])
    qk, kk = lax.map(jax.checkpoint(one), (split(q), split(k), split(gs)))
    return (qk.reshape((n,) + qk.shape[2:]), kk.reshape((n,) + kk.shape[2:]))


def chunk_scores(q, k, g_cum, sub: int, dtype):
    """q, k, g_cum [N, B, H, C, d] (float32; ``g_cum`` the inclusive
    cumulative log-decay within the chunk) -> (A_qk with j <= i, A_kk
    with j < i), each [N, B, H, C, C] float32."""
    *lead, c, d = q.shape
    ns = c // sub
    blocks = lambda x: x.reshape(*lead, ns, sub, d)
    qs, ks, gs = blocks(q), blocks(k), blocks(g_cum)

    # Below the diagonal, row block I against the tokens before it: both
    # factors go through the log-decay at the block's boundary (the last
    # token before it), G_i - edge <= 0 and edge - G_j <= 0.
    def off(x):
        out = [jnp.zeros((*lead, sub, c), jnp.float32)]
        for i in range(1, ns):
            edge = gs[..., i - 1, -1:, :]                      # [.., 1, d]
            rows = (x[..., i, :, :] * jnp.exp(gs[..., i, :, :] - edge))
            cols = k[..., :i * sub, :] * jnp.exp(
                edge - g_cum[..., :i * sub, :])
            a = jnp.einsum("...id,...jd->...ij", rows.astype(dtype),
                           cols.astype(dtype),
                           preferred_element_type=jnp.float32)
            out.append(jnp.pad(a, [(0, 0)] * (a.ndim - 1)
                               + [(0, c - i * sub)]))
        return jnp.stack(out, axis=-3)                # [.., ns, sub, c]

    qk_d, kk_d = _diagonal_blocks(qs, ks, gs)
    eye = jnp.eye(ns, dtype=jnp.float32)
    on = lambda x: (x[..., :, :, None, :] * eye[:, None, :, None]).reshape(
        *lead, c, c)
    return (off(qs).reshape(*lead, c, c) + on(qk_d),
            off(ks).reshape(*lead, c, c) + on(kk_d))


def kda_chunked(q, k, v, g, beta, chunk: int = 64, sub: int = 16,
                dtype=jnp.float32):
    """Heads-major: q, k [B, H, L, dk], v [B, H, L, dv], g [B, H, L, dk]
    (log-decay, <= 0), beta [B, H, L]; the state starts at zero.  Returns
    o [B, H, L, dv] float32.  (With the head size last and time next to
    it, a chunk is a run of whole tiles: cutting time into chunks moves no
    data, and a projection can write this layout directly.)  ``dtype``: the
    operands of the large matrix products (they accumulate in float32; the
    triangular inverse and all of the decay arithmetic are float32
    throughout).  Any L: the tail is padded with tokens that neither write
    (beta 0) nor decay (g 0)."""
    b, h, length, dk = q.shape
    pad = (-length) % chunk
    if pad:
        widen = lambda x: jnp.pad(
            x, ((0, 0), (0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 3))
        q, k, v, g, beta = map(widen, (q, k, v, g, beta))
    n = (length + pad) // chunk
    f32 = jnp.float32

    def chunks(x):                       # [B, H, L, d] -> [N, B, H, C, d]
        return jnp.moveaxis(x.reshape(b, h, n, chunk, -1), 2, 0)

    with profile_scope("kda/scan", "kernel"):
        qc, kc, gc = chunks(q.astype(f32)), chunks(k.astype(f32)), \
            chunks(g.astype(f32))
        vc = chunks(v).astype(dtype)
        bc = chunks(beta.astype(f32)[..., None])[..., 0]      # [N, B, H, C]
        g_cum = jnp.cumsum(gc, axis=-2)
        a_qk, a_kk = chunk_scores(qc, kc, g_cum, sub, dtype)
        t = unit_lower_inverse(bc[..., :, None] * a_kk) * bc[..., None, :]
        dot = functools.partial(jnp.einsum, preferred_element_type=f32)
        t = t.astype(dtype)
        into = jnp.exp(g_cum)                        # chunk start -> token
        w_v = dot("...ij,...jd->...id", t, vc)
        w_k = dot("...ij,...jd->...id", t,
                  (kc * into).astype(dtype)).astype(dtype)
        q_in = (qc * into).astype(dtype)
        g_end = g_cum[..., -1:, :]                   # token -> chunk end
        k_out = (kc * jnp.exp(g_end - g_cum)).astype(dtype)
        keep = jnp.exp(g_end[..., 0, :])             # [N, B, H, dk]

        def hand_over(state, xs):
            w_v_, w_k_, q_in_, a_qk_, k_out_, keep_ = xs
            s = state.astype(dtype)
            u = w_v_ - dot("...id,...de->...ie", w_k_, s)
            o = (dot("...id,...de->...ie", q_in_, s)
                 + dot("...ij,...je->...ie", a_qk_, u.astype(dtype)))
            state = keep_[..., None] * state + dot(
                "...id,...ie->...de", k_out_, u.astype(dtype))
            return state, o

        state0 = jnp.zeros((b, h, dk, v.shape[-1]), f32)
        _, o = lax.scan(hand_over, state0,
                        (w_v, w_k, q_in, a_qk.astype(dtype), k_out, keep))
    return jnp.moveaxis(o, 0, 2).reshape(b, h, n * chunk, -1)[:, :, :length]
