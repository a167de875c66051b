"""Ring attention: sequence/context parallelism over a mesh axis.

The reference has no attention workloads (its scale axis is geographic —
SURVEY.md §5 "long-context: absent"); this framework treats long-context
as first-class alongside the geo tiers.  Ring attention shards the
sequence across a mesh axis: each device holds one Q/K/V block, K/V blocks
rotate around the ring via ``ppermute`` while every device accumulates its
Q block's attention with a numerically-stable streaming softmax
(flash-attention style running max / normalizer).  Peak memory per device
is O(L/n · L/n) per step instead of O(L²), and each hop's transfer
overlaps the current block's compute — the same overlap discipline the
geo tiers use.

Composes with HiPS: a 3-D mesh ("dc", "worker", "sp") runs hierarchical
data parallelism across the first two axes and ring attention along the
third.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def _block(q, k, v, m, l_acc, o, scale, mask):
    """One flash-attention accumulation step.

    q: [B, Lq, H, D]; k, v: [B, Lk, H, D]; m, l_acc: [B, H, Lq]; o like q.
    mask: [Lq, Lk] boolean or None.
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if mask is not None:
        s = jnp.where(mask[None, None], s, -jnp.inf)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    # guard fully-masked rows: exp(-inf - -inf) -> use safe m
    m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    p = jnp.exp(s - m_safe[..., None])
    if mask is not None:
        p = jnp.where(mask[None, None], p, 0.0)
    corr = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_safe))
    l_new = l_acc * corr + jnp.sum(p, axis=-1)
    o_new = o * corr.transpose(0, 2, 1)[..., None] + \
        jnp.einsum("bhqk,bkhd->bqhd", p, v)
    return m_new, l_new, o_new


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   axis_name: str, causal: bool = False,
                   use_fused: Optional[bool] = None,
                   _interpret: bool = False) -> jax.Array:
    """Sequence-parallel attention; call inside shard_map.

    q, k, v: local blocks [B, L_local, H, D] (sequence sharded over
    ``axis_name``).  Returns the local output block [B, L_local, H, D].
    With ``causal=True`` positions attend only to earlier global positions
    (block-wise masking; within-block mask on the diagonal block).

    ``use_fused``: compute each hop with the fused Pallas flash block
    (`parallel/_fused_block.py`) instead of the jnp streaming block —
    same math, but the per-hop [Lq, Lk] score matrix never reaches HBM.
    Default: on TPU when the local length tiles; ``_interpret=True``
    runs the kernel in Pallas interpret mode (CPU equivalence tests).
    """
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    B, Lq, H, D = q.shape
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, q.dtype))

    hop_block = min(128, Lq)
    if use_fused is None:
        from geomx_tpu.ops.flash_attention import fused_attention_supported
        # auto-enable only on Mosaic-friendly tilings: the hop block must
        # tile L_local AND be sublane-aligned (f32 tile is 8 sublanes),
        # and the head dim lane-aligned — otherwise keep the jnp hop,
        # which works for any shape (explicit use_fused=True overrides)
        use_fused = (fused_attention_supported()
                     and Lq % hop_block == 0 and hop_block % 8 == 0
                     and D % 8 == 0)
    if use_fused and Lq % hop_block:
        raise ValueError(f"fused ring hop needs L_local ({Lq}) divisible "
                         f"by the hop block ({hop_block})")

    m0 = jnp.full((B, H, Lq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, Lq), jnp.float32)
    o0 = jnp.zeros(q.shape, jnp.float32)

    qf = q.astype(jnp.float32)
    tri = jnp.tril(jnp.ones((Lq, Lq), bool))

    if use_fused:
        from geomx_tpu.parallel._fused_block import fused_block

        def hop(m_, l_, o_, kk, vv, diag):
            return fused_block(qf, kk, vv, m_, l_, o_, float(1.0 /
                               np.sqrt(D)), diag, hop_block, _interpret)
    else:
        def hop(m_, l_, o_, kk, vv, diag):
            return _block(qf, kk, vv, m_, l_, o_, scale,
                          tri if diag else None)

    def body(step, carry):
        m, l_acc, o, kk, vv = carry
        # kv block currently held came from device (idx - step) mod n
        src = (idx - step) % n
        if causal:
            # diagonal block: lower-triangular; earlier blocks: full;
            # later blocks: empty
            def masked(m_, l_, o_):
                return hop(m_, l_, o_, kk, vv, True)

            def full(m_, l_, o_):
                return hop(m_, l_, o_, kk, vv, False)

            def skip(m_, l_, o_):
                return m_, l_, o_

            m, l_acc, o = lax.cond(
                src == idx, masked,
                lambda m_, l_, o_: lax.cond(src < idx, full, skip, m_, l_, o_),
                m, l_acc, o)
        else:
            m, l_acc, o = hop(m, l_acc, o, kk, vv, False)
        # rotate K/V around the ring (skip after the final block)
        perm = [(i, (i + 1) % n) for i in range(n)]
        kk = lax.ppermute(kk, axis_name, perm)
        vv = lax.ppermute(vv, axis_name, perm)
        return m, l_acc, o, kk, vv

    m, l_acc, o, _, _ = lax.fori_loop(
        0, n, body, (m0, l0, o0, k.astype(jnp.float32), v.astype(jnp.float32)))
    l_acc = jnp.maximum(l_acc, 1e-20)
    out = o / l_acc.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def full_attention_reference(q, k, v, causal: bool = False):
    """Dense O(L^2) attention for correctness tests."""
    B, L, H, D = q.shape
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.asarray(D, jnp.float32))
    if causal:
        mask = jnp.tril(jnp.ones((L, L), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)
