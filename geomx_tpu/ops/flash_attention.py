"""Fused flash-attention Pallas kernel.

The reference has no attention operator at all (its workloads are CNNs;
SURVEY.md §5 "long-context: absent") — this kernel backs the framework's
first-class long-context path (`models/seq_classifier.py`,
`parallel/ring_attention.py`) with a TPU-native fused implementation:
one pass over KV tiles with an online softmax held in VMEM scratch, so
the [L, L] score matrix never touches HBM.  The unfused XLA graph
materializes scores + probabilities ([B, H, L, L] each, f32) — at
L=4096 that is 2 x 64 MB per (batch, head) of HBM traffic this kernel
never pays.

Both directions are flash on the kernel path: the forward saves the
per-row logsumexp, and `flash_attention_bwd` recomputes p per tile
from it (dq kernel over k tiles; dk/dv kernel over q tiles, with
delta = rowsum(dO * O) folding the normalizer's gradient) — the
[L, L] score matrix never exists in HBM forward OR backward.  Off-TPU
the dense jnp reference runs both ways via `jax.custom_vjp`; gradients
agree to f32 tolerance either way.

Numerics match `parallel/ring_attention.full_attention_reference` to
f32 tolerance (tests/test_flash_attention.py), including fully-masked
rows (causal + padding) which produce zeros, not NaNs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30  # large-but-finite: -inf breaks the m-correction exp


_LANES = 128  # m/l scratch is lane-replicated 2-D: TPU Mosaic has
# historically rejected 1-D VMEM refs (the upstream JAX flash kernel
# pads to (block_q, 128) for the same reason)


def _fa_kernel_nolse(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                     acc_ref, **kw):
    """Inference variant: no lse output (a Pallas output cannot be
    dead-code-eliminated by XLA, so the no-grad path must not emit
    one)."""
    _fa_kernel(q_ref, k_ref, v_ref, o_ref, None, m_ref, l_ref,
               acc_ref, **kw)


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref,
               acc_ref, *, scale, block_q, block_k, num_k, kv_len,
               causal):
    """Grid (BH, nq, nk), k innermost.  Blocks: q [1, block_q, D], k
    [1, block_k, D]; v [1, block_k, Dv] and o [1, block_q, Dv] (Dv may
    differ from D: latent attention has 192-wide q/k and 128-wide v);
    lse out [1, block_q, LANES] (lane-replicated; None on the inference
    path).  Scratch m/l [block_q, LANES] and acc [block_q, Dv] carry the
    online softmax across the k dim."""
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _accumulate():
        q = q_ref[0].astype(jnp.float32)      # [Bq, D]
        k = k_ref[0].astype(jnp.float32)      # [Bk, D]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale

        rows = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = cols < kv_len                  # padded keys contribute 0
        if causal:
            mask = mask & (cols <= rows)
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[:, :1]                 # [Bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)           # exp(NEG_INF-m) underflows,
        # but a fully-masked row has m_new = NEG_INF where it would not
        corr = jnp.exp(m_prev - m_new)        # [Bq, 1]
        l_new = l_ref[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    if causal:
        # a block whose every column is in the masked future contributes
        # nothing — skip its matmuls entirely (~half the grid at nq == nk)
        pl.when(ik * block_k <= iq * block_q + block_q - 1)(_accumulate)
    else:
        _accumulate()

    @pl.when(ik == num_k - 1)
    def _finalize():
        l_sum = jnp.maximum(l_ref[:, :1], 1e-20)  # fully-masked rows -> 0 out
        o_ref[0] = (acc_ref[:] / l_sum).astype(o_ref.dtype)
        if lse_ref is not None:
            # logsumexp per row, for the backward's p = exp(s - lse)
            lse_ref[0] = jnp.broadcast_to(m_ref[:, :1] + jnp.log(l_sum),
                                          (block_q, _LANES))


def _heads_first(x, B, H, L):
    """[B, L, H, D] -> [B*H, L, D]: one grid row per (batch, head)."""
    return x.transpose(0, 2, 1, 3).reshape(B * H, L, x.shape[-1])


def _pad_seq(x, p):
    return jnp.pad(x, ((0, 0), (0, p), (0, 0), (0, 0))) if p else x


@functools.partial(jax.jit,
                   static_argnames=("causal", "block_q", "block_k",
                                    "interpret", "with_lse"))
def flash_attention_with_lse(q: jax.Array, k: jax.Array, v: jax.Array,
                             causal: bool = False, block_q: int = 128,
                             block_k: int = 128, interpret: bool = False,
                             with_lse: bool = True):
    """Fused attention forward; returns (out [B, L, H, Dv] in q's dtype,
    lse [B, H, L] f32 or None) — lse is the per-row logsumexp the flash
    backward kernels consume.  ``with_lse=False`` (the inference path)
    skips the lse output entirely: XLA cannot dead-code-eliminate a
    Pallas output, so a discarded lse would still cost its HBM write."""
    B, Lq, H, D = q.shape
    Lk, Dv = k.shape[1], v.shape[-1]
    scale = 1.0 / float(np.sqrt(D))

    bq, bk = min(block_q, Lq), min(block_k, Lk)
    pq, pk = (-Lq) % bq, (-Lk) % bk
    qp, kp, vp = _pad_seq(q, pq), _pad_seq(k, pk), _pad_seq(v, pk)
    Lqp, Lkp = Lq + pq, Lk + pk
    nq, nk = Lqp // bq, Lkp // bk

    qh = _heads_first(qp, B, H, Lqp)
    kh = _heads_first(kp, B, H, Lkp)
    vh = _heads_first(vp, B, H, Lkp)

    common = dict(scale=scale, block_q=bq, block_k=bk, num_k=nk,
                  kv_len=Lk, causal=causal)
    ospec = pl.BlockSpec((1, bq, Dv), lambda bh, iq, ik: (bh, iq, 0))
    lspec = pl.BlockSpec((1, bq, _LANES), lambda bh, iq, ik: (bh, iq, 0))
    res = pl.pallas_call(
        functools.partial(_fa_kernel if with_lse else _fa_kernel_nolse,
                          **common),
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, iq, ik: (bh, ik, 0)),
            pl.BlockSpec((1, bk, Dv), lambda bh, iq, ik: (bh, ik, 0)),
        ],
        out_specs=[ospec, lspec] if with_lse else ospec,
        out_shape=(
            [jax.ShapeDtypeStruct((B * H, Lqp, Dv), q.dtype),
             jax.ShapeDtypeStruct((B * H, Lqp, _LANES), jnp.float32)]
            if with_lse
            else jax.ShapeDtypeStruct((B * H, Lqp, Dv), q.dtype)),
        scratch_shapes=[
            pltpu.VMEM((bq, _LANES), jnp.float32),  # running max m
            pltpu.VMEM((bq, _LANES), jnp.float32),  # normalizer l
            pltpu.VMEM((bq, Dv), jnp.float32),      # output accumulator
        ],
        interpret=interpret,
    )(qh, kh, vh)
    out, lse = res if with_lse else (res, None)
    out = out.reshape(B, H, Lqp, Dv).transpose(0, 2, 1, 3)[:, :Lq]
    if with_lse:
        lse = lse[..., 0].reshape(B, H, Lqp)[..., :Lq]
    return out, lse


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = False, block_q: int = 128,
                    block_k: int = 128,
                    interpret: bool = False) -> jax.Array:
    """Fused attention forward: softmax(QK^T / sqrt(D)) V.

    q, k: [B, L, H, D]; v: [B, L, H, Dv], Dv = D or not (L may differ
    between q and k/v only via padding — the kernel masks keys past k's
    length).  Returns [B, L, H, Dv] in q's dtype.  Gradients flow via the flash backward of
    :func:`fused_attention`; differentiate THAT, not this.
    """
    return flash_attention_with_lse(q, k, v, causal=causal,
                                    block_q=block_q, block_k=block_k,
                                    interpret=interpret,
                                    with_lse=False)[0]


def _bwd_masks(iq, ik, block_q, block_k, q_len, kv_len, causal):
    """Shared [Bq, Bk] validity mask for the backward tiles: real q rows,
    real k cols, and (optionally) the causal triangle."""
    rows = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    cols = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = (rows < q_len) & (cols < kv_len)
    if causal:
        mask = mask & (cols <= rows)
    return mask


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_ref, *, scale, block_q, block_k, num_k, q_len, kv_len,
               causal):
    """dq = sum_k ds @ K * scale, ds = p * (dO V^T - delta).  Grid
    (BH, nq, nk), k innermost; dq accumulates in VMEM scratch."""
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _accumulate():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = _bwd_masks(iq, ik, block_q, block_k, q_len, kv_len, causal)
        p = jnp.where(mask, jnp.exp(s - lse_ref[0, :, :1]), 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, :, :1])
        acc_ref[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    if causal:
        pl.when(ik * block_k <= iq * block_q + block_q - 1)(_accumulate)
    else:
        _accumulate()

    @pl.when(ik == num_k - 1)
    def _finalize():
        dq_ref[0] = acc_ref[:]


def _dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, scale, block_q,
                block_k, num_q, q_len, kv_len, causal):
    """dk = sum_q ds^T @ Q * scale; dv = sum_q p^T @ dO.  Grid
    (BH, nk, nq), q innermost; dk/dv accumulate in VMEM scratch."""
    ik = pl.program_id(1)
    iq = pl.program_id(2)

    @pl.when(iq == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _accumulate():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = _bwd_masks(iq, ik, block_q, block_k, q_len, kv_len, causal)
        p = jnp.where(mask, jnp.exp(s - lse_ref[0, :, :1]), 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, :, :1])
        dk_acc[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        dv_acc[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        # a q tile entirely above the diagonal of this k tile never
        # attends to it
        pl.when(iq * block_q + block_q - 1 >= ik * block_k)(_accumulate)
    else:
        _accumulate()

    @pl.when(iq == num_q - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:]
        dv_ref[0] = dv_acc[:]


@functools.partial(jax.jit,
                   static_argnames=("causal", "block_q", "block_k",
                                    "interpret"))
def flash_attention_bwd(q, k, v, out, lse, do, causal: bool = False,
                        block_q: int = 128, block_k: int = 128,
                        interpret: bool = False):
    """Flash backward: (dq, dk, dv) in f32, without ever materializing
    the [L, L] score matrix — p is recomputed per tile from the
    forward's logsumexp (the standard flash-attention backward;
    delta_i = rowsum(dO_i * O_i) folds the softmax normalizer's
    gradient).  v, out and do may have a head size of their own (Dv);
    equal sizes give the kernels they always gave."""
    B, Lq, H, D = q.shape
    Lk, Dv = k.shape[1], v.shape[-1]
    scale = 1.0 / float(np.sqrt(D))
    # delta: [B, H, Lq] — cheap elementwise jnp, no reason to fuse
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).transpose(0, 2, 1)

    bq, bk = min(block_q, Lq), min(block_k, Lk)
    pq, pk = (-Lq) % bq, (-Lk) % bk
    qp, dop = _pad_seq(q, pq), _pad_seq(do, pq)
    kp, vp = _pad_seq(k, pk), _pad_seq(v, pk)
    Lqp, Lkp = Lq + pq, Lk + pk
    nq, nk = Lqp // bq, Lkp // bk

    qh = _heads_first(qp, B, H, Lqp)
    doh = _heads_first(dop, B, H, Lqp)
    kh = _heads_first(kp, B, H, Lkp)
    vh = _heads_first(vp, B, H, Lkp)

    def rows_first(x):  # [B, H, Lq] -> [B*H, Lqp, LANES] lane-replicated
        xp = jnp.pad(x, ((0, 0), (0, 0), (0, pq))) if pq else x
        return jnp.broadcast_to(
            xp.reshape(B * H, Lqp, 1), (B * H, Lqp, _LANES))

    lseh, deltah = rows_first(lse), rows_first(delta)

    common = dict(scale=scale, block_q=bq, block_k=bk, q_len=Lq,
                  kv_len=Lk, causal=causal)
    qspec = pl.BlockSpec((1, bq, D), lambda bh, i, j: (bh, i, 0))
    dospec = pl.BlockSpec((1, bq, Dv), lambda bh, i, j: (bh, i, 0))
    kspec_q = pl.BlockSpec((1, bk, D), lambda bh, i, j: (bh, j, 0))
    vspec_q = pl.BlockSpec((1, bk, Dv), lambda bh, i, j: (bh, j, 0))
    rspec = pl.BlockSpec((1, bq, _LANES), lambda bh, i, j: (bh, i, 0))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, num_k=nk, **common),
        grid=(B * H, nq, nk),
        in_specs=[qspec, kspec_q, vspec_q, dospec, rspec, rspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((B * H, Lqp, D), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret,
    )(qh, kh, vh, doh, lseh, deltah)

    # dkv grid: (BH, nk, nq) — q innermost; index maps swap accordingly
    kspec_k = pl.BlockSpec((1, bk, D), lambda bh, i, j: (bh, i, 0))
    vspec_k = pl.BlockSpec((1, bk, Dv), lambda bh, i, j: (bh, i, 0))
    qspec_k = pl.BlockSpec((1, bq, D), lambda bh, i, j: (bh, j, 0))
    dospec_k = pl.BlockSpec((1, bq, Dv), lambda bh, i, j: (bh, j, 0))
    rspec_k = pl.BlockSpec((1, bq, _LANES), lambda bh, i, j: (bh, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, num_q=nq, **common),
        grid=(B * H, nk, nq),
        in_specs=[kspec_k, vspec_k, qspec_k, dospec_k, rspec_k, rspec_k],
        out_specs=[kspec_k, vspec_k],
        out_shape=[jax.ShapeDtypeStruct((B * H, Lkp, D), jnp.float32),
                   jax.ShapeDtypeStruct((B * H, Lkp, Dv), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, Dv), jnp.float32)],
        interpret=interpret,
    )(kh, vh, qh, doh, lseh, deltah)

    def back(x, L, Lp):
        return x.reshape(B, H, Lp, x.shape[-1]).transpose(0, 2, 1, 3)[:, :L]

    return back(dq, Lq, Lqp), back(dk, Lk, Lkp), back(dv, Lk, Lkp)


def fused_attention_supported() -> bool:
    """True when the native kernel path is active (ops/dispatch.py: on a
    TPU)."""
    from geomx_tpu.ops.dispatch import kernel_mode
    return kernel_mode() == "native"


def _dense(q, k, v, causal):
    """f32-upcast dense attention — delegates the math to the numerical
    baseline (`full_attention_reference`), so the backward's gradients
    match it by construction."""
    from geomx_tpu.parallel.ring_attention import full_attention_reference
    return full_attention_reference(
        q.astype(jnp.float32), k.astype(jnp.float32),
        v.astype(jnp.float32), causal=causal).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def fused_attention(q, k, v, causal: bool = False,
                    interpret: bool = False):
    """Differentiable attention with platform dispatch built in: the
    Pallas kernels on TPU (or under ``interpret=True``), the dense jnp
    reference elsewhere — callers never gate on platform.  On the
    kernel path BOTH directions are flash: the backward recomputes p
    per tile from the forward's saved logsumexp, so the [L, L] score
    matrix never exists in HBM forward or backward."""
    if interpret or fused_attention_supported():
        return flash_attention(q, k, v, causal=causal,
                               interpret=interpret)
    return _dense(q, k, v, causal)


def _fused_fwd(q, k, v, causal, interpret):
    if interpret or fused_attention_supported():
        out, lse = flash_attention_with_lse(q, k, v, causal=causal,
                                            interpret=interpret)
        return out, (q, k, v, out, lse)
    return _dense(q, k, v, causal), (q, k, v, None, None)


def _fused_bwd(causal, interpret, res, g):
    q, k, v, out, lse = res
    if lse is not None:  # kernel path: flash backward
        dq, dk, dv = flash_attention_bwd(q, k, v, out, lse, g,
                                         causal=causal,
                                         interpret=interpret)
        return (dq.astype(q.dtype), dk.astype(k.dtype),
                dv.astype(v.dtype))
    _, vjp = jax.vjp(lambda q_, k_, v_: _dense(q_, k_, v_, causal),
                     q, k, v)
    return vjp(g)


fused_attention.defvjp(_fused_fwd, _fused_bwd)
