"""Ulysses-style all-to-all sequence parallelism.

The second canonical long-context strategy next to ring attention
(parallel/ring_attention.py): instead of rotating K/V blocks around a
ring, one ``all_to_all`` re-shards the activations from
sequence-sharding to HEAD-sharding, every device runs ordinary full
attention over the complete sequence for its subset of heads, and a
second ``all_to_all`` re-shards back.  Two collectives total per
attention call (vs n-1 ppermute hops), full-sequence attention math on
device (any masking/bias works unchanged), at the price of requiring
num_heads % axis_size == 0.

On TPU the all-to-alls ride ICI; composes with HiPS exactly like ring
attention does: a 3-D mesh ("dc", "worker", "sp") runs hierarchical data
parallelism across the first two axes and sequence parallelism along the
third — use whichever of ring/ulysses fits the head count and sequence
length.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from geomx_tpu.parallel.ring_attention import _block


def _fused_block_aligned(seq_len: int) -> bool:
    """Mirror of ring_attention's hop-block gate for the post-all_to_all
    full sequence: sequences of 128 and more always pass; shorter ones
    only when 8-aligned (the f32 tile's sublanes), else the jnp streaming
    path serves.  The flash kernels themselves now pad any length to
    whole 128-row blocks (`ops.flash_attention.attention_plan`), so this
    gate only keeps the two paths' split where it was."""
    return min(128, seq_len) % 8 == 0


def _streaming_attention(q, k, v, causal: bool,
                         block: int = 1024) -> jax.Array:
    """Full-sequence attention with a flash-style streaming softmax over
    K/V blocks: peak score memory is O(L * block) per head, never the
    O(L^2) a dense softmax would materialize — this is the on-device
    half of ulysses for the long sequences the module exists for."""
    B, L, H, D = q.shape
    blk = min(block, L)
    nb = -(-L // blk)
    pad = nb * blk - L
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    q_pos = jnp.arange(L)

    m0 = jnp.full((B, H, L), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, L), jnp.float32)
    o0 = jnp.zeros(q.shape, jnp.float32)

    def body(i, carry):
        m, l_acc, o = carry
        kk = lax.dynamic_slice_in_dim(kf, i * blk, blk, axis=1)
        vv = lax.dynamic_slice_in_dim(vf, i * blk, blk, axis=1)
        k_pos = i * blk + jnp.arange(blk)
        mask = k_pos[None, :] < L  # padded tail is never attended
        if causal:
            mask = mask & (q_pos[:, None] >= k_pos[None, :])
        else:
            mask = jnp.broadcast_to(mask, (L, blk))
        return _block(qf, kk, vv, m, l_acc, o, scale, mask)

    m, l_acc, o = lax.fori_loop(0, nb, body, (m0, l0, o0))
    l_acc = jnp.maximum(l_acc, 1e-20)
    return o / l_acc.transpose(0, 2, 1)[..., None]


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      axis_name: str, causal: bool = False,
                      use_fused: Optional[bool] = None,
                      _interpret: bool = False) -> jax.Array:
    """Sequence-parallel attention via head/sequence all-to-all
    re-sharding; call inside shard_map.

    q, k, v: local blocks [B, L_local, H, D] (sequence sharded over
    ``axis_name``); requires H % axis_size == 0.  Returns the local
    output block [B, L_local, H, D], numerically identical to dense
    attention over the full sequence.

    ``use_fused``: run the on-device attention with the fused Pallas
    flash kernels via `ops.fused_attention` (default: on TPU with a
    lane-aligned head dim).  Flash in BOTH directions: the backward
    recomputes p per tile from the
    forward's logsumexp (`ops.flash_attention_bwd`), so the [L, L]
    scores never exist in HBM — unlike autodiff of the streaming jnp
    path, whose scan residuals total O(L^2).
    """
    n = lax.psum(1, axis_name)
    B, Lq, H, D = q.shape
    if H % n != 0:
        raise ValueError(f"ulysses needs heads ({H}) divisible by the "
                         f"sequence axis size ({n})")
    if use_fused is None:
        from geomx_tpu.ops.flash_attention import fused_attention_supported
        # both alignments mirror ring_attention's auto-gate: Mosaic
        # needs the head dim lane-aligned AND the kernel's seq block
        # sublane-aligned.  The fused call sees the FULL sequence
        # (Lq * n after the all_to_all), so the gate checks the padded
        # block of that length; misaligned shapes fall back to
        # _streaming_attention (explicit use_fused=True overrides)
        use_fused = (fused_attention_supported() and D % 8 == 0
                     and _fused_block_aligned(Lq * n))

    # ONE all_to_all for q/k/v stacked: [3, B, L/n, H, D] -> [3, B, L,
    # H/n, D] — each device trades its sequence shard of every head for
    # the full sequence of its head shard (received chunks concatenate
    # in device order = global sequence order)
    qkv = lax.all_to_all(jnp.stack([q, k, v]), axis_name,
                         split_axis=3, concat_axis=2, tiled=True)
    if use_fused:
        from geomx_tpu.ops.flash_attention import fused_attention
        out = fused_attention(qkv[0], qkv[1], qkv[2], causal, _interpret)
    else:
        out = _streaming_attention(qkv[0], qkv[1], qkv[2], causal)
    # downcast BEFORE the return trip: all_to_all is pure data movement,
    # so casting first is bit-identical and halves the wire bytes for
    # sub-f32 activations.  [B, L, H/n, D] -> [B, L/n, H, D]
    return lax.all_to_all(out.astype(q.dtype), axis_name,
                          split_axis=1, concat_axis=2, tiled=True)
