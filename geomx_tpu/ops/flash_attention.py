"""Fused flash-attention Pallas kernels.

The reference has no attention operator at all (its workloads are CNNs;
SURVEY.md §5 "long-context: absent") — these kernels back the framework's
first-class long-context path (`models/seq_classifier.py`,
`models/kimi_linear.py`, `models/afmoe.py`, `parallel/ulysses.py`) with a
TPU-native fused implementation: the [L, L] score matrix never touches
HBM, forward or backward.

**How much a grid step does** (docs/kernels.md has the long form).  The
kernels read q, k, v as `[B, L, H * D]` (the caller's `[B, L, H, D]`, no
transpose) and a step takes a block of rows for a *group of heads*: a
lane-aligned slab of `heads * D` columns.  :func:`attention_plan`, a pure
function of the shapes and the dtype, picks the blocks and the heads a
step under :data:`VMEM_BUDGET`; `block_q` / `block_k` given by a caller
are honoured.  The grid walks a static list of the (q block, k block)
pairs that hold a score, so a causal call neither visits nor fetches the
blocks above the diagonal, and only the pairs that cross the diagonal or
the end of a padded length run the masked body.

**Grouped-query heads and a causal band.**  k and v may have fewer heads
than q (`Hq % Hkv == 0`; query head n reads key/value head `n // (Hq //
Hkv)`): they cross HBM with their own `Hkv` heads and no wider copy
exists anywhere.  A step's query heads share one key/value head, or are
whole groups; the kernels that make dk and dv take whole groups a step
and sum a group's contributions in their float32 accumulators.  `window`
(causal calls only) keeps key j for query i iff `0 <= i - j < window`:
the pairs wholly under the band leave the list like those above the
diagonal, and the pairs that cross its lower edge run the masked body,
forward, dq and dk/dv alike.  Equal head counts and no window give the
specs and the lists they gave before either existed.

**Precision.**  Every product takes its operands in the dtype the caller
gave (bf16 in, bf16 on the MXU) and accumulates in float32
(`preferred_element_type`); `p` and `ds` are rounded to that dtype for
the products they feed, as every other product of a bf16 model is.  The
scores, the running max, the normaliser, `lse`, `delta`, `exp` and every
accumulator are float32; the softmax scale is applied to the float32
scores and to the accumulated `dq` / `dk`, never to an operand.  Float32
callers get float32 operands at the MXU's default precision, as before.

**Backward.**  The backward works on transposed scores (`s^T = K Q^T`),
so that `lse` and `delta` are rows that broadcast down the sublanes and
only `dq` needs a transpose, a small one.  ONE kernel computes `s`, `p`,
`dp`, `ds` once a block pair and all three gradients from them (five
products) wherever a run of its key-major walk finishes a dq block:

- one block pair is the whole sequence (L <= 512), with
  `delta = rowsum(P * dP)` (= rowsum(dO * O)) taken inside it;
- a causal call of several pairs with `block_q == block_k` and `Lq ==
  Lk`.  The walk takes key block 0 against q blocks 0, 1, ..., then key
  block 1 against q blocks 1, 2, ...: the run of key block j opens on the
  diagonal pair (j, j), and dq block j takes from the runs 0..j (under a
  band from j - w..j) and from no later one, so that pair is the last
  that adds to it.  dq^T of EVERY q block stays in VMEM as float32
  `[nq, heads * D, bq]` for the walk of a (batch, head group); dq's out
  block is indexed by the key block, so a run holds it: the diagonal
  pair writes the scaled, transposed block there and Pallas copies it
  out when the run ends.  No partial dq crosses HBM.  The array costs
  `4 * L * heads * D` bytes (16 MiB for one 256-wide head of 16,384
  rows; for grouped heads the kernel's heads are whole groups, so a
  group of eight 128-wide heads keeps 32 MiB at 8,192 rows and 64 MiB
  at 16,384), beside what VMEM_BUDGET counts, and the call raises
  Mosaic's VMEM limit to the plan's bytes + _VMEM_HEADROOM:
  :func:`attention_plan` takes the one kernel where that limit is at
  most ONE_KERNEL_VMEM, a share of the core's VMEM, under a band as
  without one.

Every other call runs a dq kernel and a dk/dv kernel (seven products:
`s^T` and `dP^T` twice).  Past one pair `delta` comes from XLA as rows of
L values.  `lse` crosses HBM as `[B, H, L]` float32 rows.  The sums run
in the same order in either form (dq over key blocks ascending, dk and dv
over q blocks ascending).  Off a TPU the dense jnp reference runs both
ways via `jax.custom_vjp`.

Numerics match `parallel/ring_attention.full_attention_reference`
(tests/test_flash_attention.py), including fully-masked rows (causal +
padding) which produce zeros, not NaNs.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from geomx_tpu.utils.profiler import profile_scope

_NEG_INF = -1e30  # large-but-finite: -inf breaks the m-correction exp
_LANES = 128

# What a kernel's blocks, scratch and score-sized temporaries may take:
# three quarters of the 16 MiB of VMEM that Mosaic gives a v5e kernel
# unasked.
VMEM_BUDGET = 12 * 2 ** 20
MAX_BLOCK = 512      # rows of q or k a step: a [512, 512] f32 score tile
MAX_HEADS = 8        # heads a step: bounds the unrolled code
# What ONE backward kernel of several block pairs may ask Mosaic for in
# all, since its call sets the limit itself: what it streams, the float32
# dq^T of every q block that it keeps for its whole walk, _VMEM_HEADROOM.
# Three quarters of a v5e core's 128 MiB: a group of eight 128-wide heads
# at 16,384 rows asks for 92.5 MiB (64 of them dq^T), at 32,768 for 156.
ONE_KERNEL_VMEM = 96 * 2 ** 20
# over the plan's bytes, for what Mosaic keeps that the plan does not count
_VMEM_HEADROOM = 16 * 2 ** 20


class AttentionPlan(NamedTuple):
    """What one grid step does (:func:`attention_plan`)."""
    block_q: int
    block_k: int
    heads: int              # heads a step, a divisor of H
    fused_backward: bool    # one backward kernel, else dq and dk/dv kernels
    vmem_bytes: int         # the largest kernel's estimate
    resident_bytes: int = 0  # of them, the one backward kernel's dq^T

    @property
    def backward_kernels(self) -> str:
        """`one` / `two`: what a trace's span and the timing tool print."""
        return "one" if self.fused_backward else "two"


def _default_block(length: int) -> int:
    """The whole (128-padded) sequence up to MAX_BLOCK rows, else the
    largest of MAX_BLOCK, its half, ... down to 128 that divides it."""
    padded = -(-length // _LANES) * _LANES
    if padded <= MAX_BLOCK:
        return padded
    block = MAX_BLOCK
    while padded % block:
        block //= 2
    return block


def _kv_heads(heads: int, group: int) -> int:
    """Key/value heads behind `heads` query heads of one step: one while
    the step stays inside a group, else the whole groups' own."""
    return max(heads // group, 1)


def _vmem_bytes(bq, bk, heads, group, d, dv, itemsize, one_backward):
    """Bytes of VMEM the largest of the kernels asks for: double-buffered
    blocks in and out, the float32 accumulators (one q block's dq^T; the
    further blocks ONE backward kernel of a long sequence keeps are
    :func:`_resident_dq_bytes`), and the [bk, bq] temporaries (s, p, dp,
    ds in float32, p and ds rounded).  `heads` query heads a step read
    `_kv_heads` key/value heads; a kernel that makes dk and dv takes whole
    groups (`max(heads, group)` query heads).  `one_backward`: one kernel
    makes dq, dk and dv, and is the largest; else a kernel makes either dq
    or dk and dv, the forward keeps a lane-replicated running max and
    normaliser, and with equal head counts and equal blocks the dk/dv
    kernel is the largest."""
    def blocks(g):
        qk, vv = g * d, g * dv
        kk, kv = _kv_heads(g, group) * d, _kv_heads(g, group) * dv
        return qk, vv, kk, kv, bq * (qk + vv) + bk * (kk + kv)

    scores = bq * bk * (4 * 4 + 2 * itemsize)
    qk, vv, kk, kv, blocks_in = blocks(max(heads, group))
    if one_backward:
        out = bq * qk + bk * (kk + kv)
        return 2 * itemsize * (blocks_in + out) + 4 * out + scores
    out = bk * (kk + kv)
    dkv = 2 * itemsize * (blocks_in + out) + 4 * out + scores
    qk, vv, kk, kv, blocks_in = blocks(heads)
    dq = 2 * itemsize * (blocks_in + bq * qk) + 4 * bq * qk + scores
    fwd = (2 * itemsize * blocks_in + 4 * bq * vv
           + 2 * 4 * heads * bq * _LANES + bq * bk * (2 * 4 + itemsize))
    return max(dkv, dq, fwd)


def _resident_dq_bytes(q_blocks, bq, heads, group, d):
    """The float32 dq^T of `q_blocks` blocks that one backward kernel
    keeps: [whole groups' heads x d, bq] each."""
    return 4 * q_blocks * bq * max(heads, group) * d


def attention_plan(q_len: int, kv_len: int, heads: int, d: int, dv: int,
                   dtype, causal: bool, block_q: Optional[int] = None,
                   block_k: Optional[int] = None,
                   kv_heads: Optional[int] = None) -> AttentionPlan:
    """Blocks, heads a grid step and the backward's form, from the shapes
    alone.

    Blocks: a given `block_q` / `block_k` is honoured (capped at the
    length); else :func:`_default_block`.  Heads a step: the largest
    divisor of H, at most MAX_HEADS, whose slab of `heads * D` (and
    `heads * Dv`) columns is whole 128-lane tiles (or all of them) and
    whose kernels fit VMEM_BUDGET; the smallest such slab where none
    fits.  With `kv_heads` < H (grouped-query heads) a step's query heads
    share one key/value head or are whole groups, and the key/value slab
    is lane-aligned too; the kernels that make dk and dv then take
    `max(heads, H // kv_heads)` query heads.

    One backward kernel (`fused_backward`) where a run of its walk
    finishes a dq block and it fits: one block pair is the whole sequence
    and the kernel is one of those VMEM_BUDGET chooses the heads for
    (whole groups of them); or the call is `causal` with `block_q ==
    block_k` and `q_len == kv_len` (the key-major walk opens each run on
    the diagonal pair, the last that adds to that dq block) and what the
    kernel asks for in all, the bytes whole groups of heads stream, the
    dq^T of every q block (`resident_bytes`) and _VMEM_HEADROOM, is at
    most ONE_KERNEL_VMEM.  Else a dq kernel and a dk/dv kernel.  Beyond
    that `causal` (and a window) choose no size: they shorten the list of
    block pairs the grid walks (:func:`_block_pairs`)."""
    kv_heads = kv_heads or heads
    if heads % kv_heads:
        raise ValueError(f"{heads} query heads over {kv_heads} key/value "
                         f"heads: not whole groups")
    group = heads // kv_heads
    bq = min(block_q, q_len) if block_q else _default_block(q_len)
    bk = min(block_k, kv_len) if block_k else _default_block(kv_len)
    nq = -(-q_len // bq)
    one_pair = nq == 1 and -(-kv_len // bk) == 1
    fused = one_pair
    itemsize = jnp.dtype(dtype).itemsize

    def aligned(g, all_of):
        return g == all_of or ((g * d) % _LANES == 0
                               and (g * dv) % _LANES == 0)

    slabs = [g for g in range(1, heads + 1)
             if heads % g == 0 and (g % group == 0 or group % g == 0)
             and aligned(g, heads)
             and aligned(_kv_heads(g, group), kv_heads)]
    cost = lambda g: _vmem_bytes(bq, bk, g, group, d, dv, itemsize, fused)
    fit = lambda g: g <= MAX_HEADS and cost(g) <= VMEM_BUDGET
    if fused and group > 1 and not any(map(fit, slabs)):
        fused = False       # whole groups do not fit one backward kernel
    fits = [g for g in slabs if fit(g)]
    g = max(fits) if fits else min(slabs)
    resident = _resident_dq_bytes(nq, bq, g, group, d)
    if fused:       # one pair: its one block of dq^T is in cost(g)
        return AttentionPlan(bq, bk, g, True, cost(g), resident)
    if not one_pair and causal and bq == bk and q_len == kv_len:
        one_kernel = (_vmem_bytes(bq, bk, g, group, d, dv, itemsize, True)
                      + resident - resident // nq)
        if one_kernel + _VMEM_HEADROOM <= ONE_KERNEL_VMEM:
            return AttentionPlan(bq, bk, g, True, one_kernel, resident)
    return AttentionPlan(bq, bk, g, False, cost(g))


def _band(window, causal: bool, kv_len: int):
    """`window` as the kernels take it: None where it hides nothing (no
    window, or one that reaches past the first key)."""
    if window is None:
        return None
    if not causal or window < 1:
        raise ValueError("a window is a causal band of at least one key")
    return None if window >= kv_len else int(window)


def _block_pairs(nq, nk, bq, bk, q_len, kv_len, causal, k_inner,
                 window=None):
    """The (q block, k block) pairs that hold a score, as the grid's last
    axis walks them: q-major with k inner (forward, dq) or k-major with q
    inner (dk/dv).  `window`: query i sees key j iff 0 <= i - j < window,
    so the pairs wholly under the band hold none.  Returns int32 lists
    `(qi, kj, flags)` for scalar prefetch; flags: 1 the first pair of its
    run (same outer block), 2 the last, 4 the pair needs the mask (it
    crosses the diagonal or the band's lower edge, or holds padded rows or
    columns); and `bodies`, the kinds of pair the list holds (False
    unmasked, True masked), which are the bodies a kernel needs
    (:func:`_run_bodies`)."""
    pairs = [(i, j) for i in range(nq) for j in range(nk)
             if (not causal or j * bk <= i * bq + bq - 1)
             and (window is None or j * bk + bk - 1 > i * bq - window)]
    outer = 0 if k_inner else 1
    pairs.sort(key=lambda p: (p[outer], p[1 - outer]))
    flags = []
    for t, (i, j) in enumerate(pairs):
        first = t == 0 or pairs[t - 1][outer] != pairs[t][outer]
        last = t == len(pairs) - 1 or pairs[t + 1][outer] != pairs[t][outer]
        masked = ((causal and j * bk + bk - 1 > i * bq)
                  or (window is not None
                      and i * bq + bq - 1 - j * bk >= window)
                  or (j + 1) * bk > kv_len or (i + 1) * bq > q_len)
        flags.append(first + 2 * last + 4 * masked)
    as_i32 = lambda xs: np.asarray(xs, np.int32)
    return ((as_i32([p[0] for p in pairs]), as_i32([p[1] for p in pairs]),
             as_i32(flags)), sorted({bool(f & 4) for f in flags}))


def _window(h, d, width):
    """Head h's columns [h d, (h + 1) d) of a slab `width` wide: the
    128-aligned window (lo, hi) that holds them and where they sit in it
    (a, b).  A 64-wide head shares its window with its neighbour; a
    192-wide one takes two tiles, one of them shared."""
    start, stop = h * d, (h + 1) * d
    lo = start // _LANES * _LANES
    hi = min(-(-stop // _LANES) * _LANES, width)
    return lo, hi, start - lo, stop - lo


def _windows(h, group, d, q_width, kv_width):
    """Query head h's window in its slab and that of its key/value head
    `h // group` in theirs, each as :func:`_window` gives it.  Where the
    two heads sit differently in their windows (grouped heads narrower
    than a lane tile) both are the heads' own columns."""
    kh = h // group
    at_q, at_kv = _window(h, d, q_width), _window(kh, d, kv_width)
    if at_q[1] - at_q[0] != at_kv[1] - at_kv[0] or at_q[2:] != at_kv[2:]:
        at_q, at_kv = (h * d, (h + 1) * d, 0, d), (kh * d, (kh + 1) * d, 0, d)
    return at_q, at_kv


def _only(x, a, b, axis=1):
    """x with everything outside [a, b) along `axis` set to zero: the
    other heads of a shared window drop out of a product's contraction
    (a 64-deep contraction costs the MXU what a 128-deep one does)."""
    if a == 0 and b == x.shape[axis]:
        return x
    at = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    keep = (at >= a) & (at < b)
    return jnp.where(keep, x.astype(jnp.float32), 0.0).astype(x.dtype)


def _put(ref, lo, hi, a, b, new, axis=1, add=False):
    """Write (or add) `new` into head columns [a, b) of window [lo, hi) of
    a float32 scratch; the window's other columns keep what they hold.
    `axis` 0: the same on rows (the transposed dq)."""
    at = (slice(None), slice(lo, hi)) if axis else (slice(lo, hi),
                                                    slice(None))
    if a == 0 and b == hi - lo:
        ref[at] = ref[at] + new if add else new
        return
    old = ref[at]
    index = jax.lax.broadcasted_iota(jnp.int32, old.shape, axis)
    keep = (index >= a) & (index < b)
    ref[at] = jnp.where(keep, old + new if add else new, old)


_NT = (((1,), (1,)), ((), ()))   # a @ b^T
_NN = (((1,), (0,)), ((), ()))   # a @ b
_TN = (((0,), (0,)), ((), ()))   # a^T @ b


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _run_bodies(step, flags, bodies):
    """`step(masked)` for the pair at hand: where the call's list holds
    both kinds of pair, two bodies under `pl.when`; else the one."""
    if len(bodies) == 1:
        step(bodies[0])
        return
    for masked in bodies:
        pl.when(((flags & 4) != 0) == masked)(
            functools.partial(step, masked))


def _fwd_kernel(qi_ref, kj_ref, fl_ref, q_ref, k_ref, v_ref, *refs, scale,
                heads, group, d, dv, bq, bk, kv_len, causal, window, single,
                with_lse, bodies):
    """One (q block, k block) pair of a group of heads.  Blocks: q
    [1, bq, heads d], k [1, bk, kv d], v [1, bk, kv dv] (kv =
    `_kv_heads(heads, group)` heads; query head h reads `h // group`), out
    [1, bq, heads dv], lse [1, heads, 1, bq] (rows; absent on the
    inference path).  Scratch: acc [bq, heads dv] float32 and, where a q
    block meets several k blocks (not `single`), running max and
    normaliser [heads, bq, LANES] (lane-replicated)."""
    refs = list(refs)
    o_ref = refs.pop(0)
    lse_ref = refs.pop(0) if with_lse else None
    acc_ref = refs.pop(0)
    m_ref, l_ref = refs if not single else (None, None)
    t = pl.program_id(2)
    i, j, flags = qi_ref[t], kj_ref[t], fl_ref[t]
    dtype = v_ref.dtype

    if not single:
        @pl.when((flags & 1) != 0)
        def _init():
            m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
            l_ref[:] = jnp.zeros_like(l_ref)
            acc_ref[:] = jnp.zeros_like(acc_ref)

    def finish(lse_cols):
        """The q block's output, and its lse: per-head [bq, 1] columns ->
        rows, one transpose a q block."""
        o_ref[0] = acc_ref[:].astype(o_ref.dtype)
        if not with_lse:
            return
        lane = jax.lax.broadcasted_iota(jnp.int32, (bq, _LANES), 1)
        packed = jnp.zeros((bq, _LANES), jnp.float32)
        for h, col in enumerate(lse_cols):
            packed = jnp.where(lane == h, col, packed)
        rows = packed.T
        for h in range(heads):
            lse_ref[0, h] = rows[h:h + 1, :]

    def step(masked):
        if masked:
            rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            mask = cols < kv_len              # padded keys contribute 0
            if causal:
                mask = mask & (cols <= rows)
            if window is not None:
                mask = mask & (rows - cols < window)
        lse_cols = []
        kv = _kv_heads(heads, group)
        for h in range(heads):
            (lo, hi, a, b), (klo, khi, _, _) = _windows(
                h, group, d, heads * d, kv * d)
            (vlo, vhi, va, vb), (kvlo, kvhi, _, _) = _windows(
                h, group, dv, heads * dv, kv * dv)
            q = _only(q_ref[0, :, lo:hi], a, b)
            s = _dot(q, k_ref[0, :, klo:khi], _NT) * scale   # [bq, bk] f32
            if masked:
                s = jnp.where(mask, s, _NEG_INF)
            m_new = jnp.max(s, axis=-1, keepdims=True)
            if not single:
                m_prev = m_ref[h, :, :1]
                m_new = jnp.maximum(m_prev, m_new)
            p = jnp.exp(s - m_new)
            if masked:
                # exp(NEG_INF - m) underflows, but a fully-masked row has
                # m_new = NEG_INF where it would not
                p = jnp.where(mask, p, 0.0)
            l_new = jnp.sum(p, axis=-1, keepdims=True)
            pv = _dot(p.astype(dtype), v_ref[0, :, kvlo:kvhi], _NN)
            if single:
                l_sum = jnp.maximum(l_new, 1e-20)  # fully-masked rows -> 0
                _put(acc_ref, vlo, vhi, va, vb, pv * (1.0 / l_sum))
                lse_cols.append(m_new + jnp.log(l_sum))
            else:
                corr = jnp.exp(m_prev - m_new)
                l_new = l_ref[h, :, :1] * corr + l_new
                _put(acc_ref, vlo, vhi, va, vb,
                     acc_ref[:, vlo:vhi] * corr + pv)
                m_ref[h] = jnp.broadcast_to(m_new, (bq, _LANES))
                l_ref[h] = jnp.broadcast_to(l_new, (bq, _LANES))
        if single:
            finish(lse_cols)

    _run_bodies(step, flags, bodies)

    if not single:
        @pl.when((flags & 2) != 0)
        def _finalize():
            lse_cols = []
            for h in range(heads):
                vlo, vhi, va, vb = _window(h, dv, heads * dv)
                l_sum = jnp.maximum(l_ref[h, :, :1], 1e-20)
                _put(acc_ref, vlo, vhi, va, vb,
                     acc_ref[:, vlo:vhi] * (1.0 / l_sum))
                lse_cols.append(m_ref[h, :, :1] + jnp.log(l_sum))
            finish(lse_cols)


def _pad_seq(x, p):
    return jnp.pad(x, ((0, 0), (0, p), (0, 0), (0, 0))) if p else x


def _slabs(x, pad):
    """[B, L, H, D] -> [B, L + pad, H D]: heads side by side, no
    transpose."""
    x = _pad_seq(x, pad)
    return x.reshape(x.shape[0], x.shape[1], -1)


class _Specs(NamedTuple):
    """BlockSpecs of one kernel: a group of query heads' slabs (rows at
    the pair's q block, `d` or `dv` wide), their key/value heads' slabs
    (rows at the pair's k block) and the per-row float32 rows (lse,
    delta); `dq_of_run`: dq's rows at the pair's k block, which a
    key-major run holds from its first pair to its last."""
    q: pl.BlockSpec
    k: pl.BlockSpec
    v: pl.BlockSpec
    o: pl.BlockSpec
    rows: pl.BlockSpec
    dq_of_run: pl.BlockSpec


class _Call(NamedTuple):
    """What the wrappers share: the plan, the operands' common dtype, the
    padding and block counts, query heads a key/value head (`group`), the
    band, and the sizes the BlockSpecs are made from."""
    plan: AttentionPlan
    dtype: jnp.dtype
    pad_q: int
    pad_k: int
    nq: int
    nk: int
    group: int
    window: Optional[int]
    d: int
    dv: int

    def specs(self, heads: int) -> _Specs:
        """For a kernel that takes `heads` query heads a grid step (axis 1
        counts such steps): their key/value slab is the step's own where
        it holds whole groups, else the one its group shares."""
        bq, bk, group = self.plan.block_q, self.plan.block_k, self.group
        kv = _kv_heads(heads, group)
        if heads % group:
            at_kv = lambda g: g * heads // group
        else:
            at_kv = lambda g: g
        at_q = lambda b, g, t, qi, kj, fl: (b, qi[t], g)
        at_k = lambda b, g, t, qi, kj, fl: (b, kj[t], at_kv(g))
        return _Specs(
            q=pl.BlockSpec((1, bq, heads * self.d), at_q),
            k=pl.BlockSpec((1, bk, kv * self.d), at_k),
            v=pl.BlockSpec((1, bk, kv * self.dv), at_k),
            o=pl.BlockSpec((1, bq, heads * self.dv), at_q),
            rows=pl.BlockSpec((1, heads, 1, bq),
                              lambda b, g, t, qi, kj, fl: (b, g, 0, qi[t])),
            dq_of_run=pl.BlockSpec(
                (1, bq, heads * self.d),
                lambda b, g, t, qi, kj, fl: (b, kj[t], g)))

    def pairs(self, q_len, kv_len, causal, k_inner):
        return _block_pairs(self.nq, self.nk, self.plan.block_q,
                            self.plan.block_k, q_len, kv_len, causal,
                            k_inner, self.window)


def _prepare(q, k, v, causal, window, block_q, block_k) -> _Call:
    B, Lq, H, D = q.shape
    Lk, Hkv, Dv = k.shape[1], k.shape[2], v.shape[-1]
    dtype = jnp.result_type(q.dtype, k.dtype, v.dtype)
    plan = attention_plan(Lq, Lk, H, D, Dv, dtype, causal, block_q, block_k,
                          kv_heads=Hkv)
    bq, bk = plan.block_q, plan.block_k
    pq, pk = (-Lq) % bq, (-Lk) % bk
    return _Call(plan, dtype, pq, pk, (Lq + pq) // bq, (Lk + pk) // bk,
                 H // Hkv, _band(window, causal, Lk), D, Dv)


def _grid_spec(pairs, grid, in_specs, out_specs, scratch_shapes):
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=grid + (len(pairs[0]),),
        in_specs=in_specs, out_specs=out_specs,
        scratch_shapes=scratch_shapes)


_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


@functools.partial(jax.jit,
                   static_argnames=("causal", "block_q", "block_k",
                                    "interpret", "with_lse", "window"))
def flash_attention_with_lse(q: jax.Array, k: jax.Array, v: jax.Array,
                             causal: bool = False,
                             block_q: Optional[int] = None,
                             block_k: Optional[int] = None,
                             interpret: bool = False,
                             with_lse: bool = True,
                             window: Optional[int] = None):
    """Fused attention forward; returns (out [B, L, H, Dv] in q's dtype,
    lse [B, H, L] f32 or None) — lse is the per-row logsumexp the flash
    backward kernels consume.  ``with_lse=False`` (the inference path)
    skips the lse output entirely: XLA cannot dead-code-eliminate a
    Pallas output, so a discarded lse would still cost its HBM write.
    ``block_q`` / ``block_k`` None: :func:`attention_plan` chooses.  k and
    v may have fewer heads than q (whole groups); ``window``: the causal
    band's width in keys."""
    B, Lq, H, D = q.shape
    Lk, Dv = k.shape[1], v.shape[-1]
    scale = 1.0 / float(np.sqrt(D))
    call = _prepare(q, k, v, causal, window, block_q, block_k)
    bq, bk, G = call.plan.block_q, call.plan.block_k, call.plan.heads
    Lqp = Lq + call.pad_q
    pairs, bodies = call.pairs(Lq, Lk, causal, k_inner=True)
    spec = call.specs(G)
    single = call.nk == 1
    out_shape = jax.ShapeDtypeStruct((B, Lqp, H * Dv), q.dtype)
    lse_shape = jax.ShapeDtypeStruct((B, H, 1, Lqp), jnp.float32)
    scratch = [pltpu.VMEM((bq, G * Dv), jnp.float32)]
    if not single:
        scratch += [pltpu.VMEM((G, bq, _LANES), jnp.float32)] * 2
    slabs = lambda x, pad: _slabs(x.astype(call.dtype), pad)
    res = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, heads=G,
                          group=call.group, d=D, dv=Dv, bq=bq, bk=bk,
                          kv_len=Lk, causal=causal, window=call.window,
                          single=single, with_lse=with_lse, bodies=bodies),
        grid_spec=_grid_spec(pairs, (B, H // G), [spec.q, spec.k, spec.v],
                             [spec.o, spec.rows] if with_lse else spec.o,
                             scratch),
        out_shape=[out_shape, lse_shape] if with_lse else out_shape,
        compiler_params=_SEMANTICS, interpret=interpret,
        name="flash_attention_fwd",
    )(*pairs, slabs(q, call.pad_q), slabs(k, call.pad_k),
      slabs(v, call.pad_k))
    out, lse = res if with_lse else (res, None)
    out = out.reshape(B, Lqp, H, Dv)[:, :Lq]
    if with_lse:
        lse = lse[:, :, 0, :Lq]
    return out, lse


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = False, block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: bool = False,
                    window: Optional[int] = None) -> jax.Array:
    """Fused attention forward: softmax(QK^T / sqrt(D)) V.

    q: [B, L, H, D]; k: [B, L, Hkv, D]; v: [B, L, Hkv, Dv], Dv = D or not,
    Hkv = H or a divisor of it (L may differ between q and k/v only via
    padding — the kernel masks keys past k's length); ``window``: with
    ``causal``, key j is seen by query i iff 0 <= i - j < window.  Returns
    [B, L, H, Dv] in q's dtype.  Gradients flow via the flash backward
    of :func:`fused_attention`; differentiate THAT, not this.
    """
    return flash_attention_with_lse(q, k, v, causal=causal,
                                    block_q=block_q, block_k=block_k,
                                    interpret=interpret, with_lse=False,
                                    window=window)[0]


def _bwd_kernel(qi_ref, kj_ref, fl_ref, q_ref, k_ref, v_ref, do_ref,
                lse_ref, *refs, scale, heads, group, d, dv, bq, bk, q_len,
                kv_len, causal, window, want_dq, want_dkv, delta_in, bodies):
    """One (q block, k block) pair of a group of heads, on transposed
    scores: s^T = K Q^T [bk, bq], so lse and delta [1, bq] broadcast down
    the sublanes.  Query head h reads key/value head `h // group`; a
    group's heads add into that head's dk and dv.  Makes dq (`want_dq`:
    pairs arrive q-major), dk and dv (`want_dkv`: k-major) or all three
    from one s, p, dp, ds.  All three: pairs arrive k-major, dq^T is kept
    for every q block of the walk, and the run of key block j opens on
    the pair (j, j), the last that adds to dq block j: that pair writes
    it to the out block the run holds (the plan's rule; one pair that is
    the whole sequence is such a walk).
    `delta_in`: delta arrives as rows [1, heads, 1, bq]; else it is
    rowsum(P * dP) over the pair, which is rowsum(dO * O) only where the
    pair holds every key.  Scratch, float32: dq^T [heads d, bq] (all
    three: [q blocks, heads d, bq]), dk [bk, kv d], dv [bk, kv dv]."""
    refs = list(refs)
    delta_ref = refs.pop(0) if delta_in else None
    dq_ref = refs.pop(0) if want_dq else None
    dk_ref, dv_ref = (refs.pop(0), refs.pop(0)) if want_dkv else (None, None)
    dqt_all = refs.pop(0) if want_dq else None
    dk_acc, dv_acc = refs if want_dkv else (None, None)
    t = pl.program_id(2)
    i, j, flags = qi_ref[t], kj_ref[t], fl_ref[t]
    dtype = q_ref.dtype
    resident = want_dq and want_dkv
    dqt_acc = dqt_all.at[i] if resident else dqt_all

    if resident:
        @pl.when(t == 0)
        def _zero_dq():
            def zero(n, carry):
                dqt_all[n] = jnp.zeros(dqt_all.shape[1:], jnp.float32)
                return carry
            jax.lax.fori_loop(0, dqt_all.shape[0], zero, 0)

    @pl.when((flags & 1) != 0)
    def _init():
        for acc in (None if resident else dqt_acc, dk_acc, dv_acc):
            if acc is not None:
                acc[:] = jnp.zeros(acc.shape, jnp.float32)

    def step(masked):
        if masked:
            rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 1)
            cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
            mask = (rows < q_len) & (cols < kv_len)
            if causal:
                mask = mask & (cols <= rows)
            if window is not None:
                mask = mask & (rows - cols < window)
        kv = _kv_heads(heads, group)
        for h in range(heads):
            (lo, hi, a, b), (klo, khi, ka, kb) = _windows(
                h, group, d, heads * d, kv * d)
            (vlo, vhi, va, vb), (kvlo, kvhi, kva, kvb) = _windows(
                h, group, dv, heads * dv, kv * dv)
            q, k = q_ref[0, :, lo:hi], k_ref[0, :, klo:khi]
            do = do_ref[0, :, vlo:vhi]
            st = _dot(_only(k, ka, kb), q, _NT) * scale      # [bk, bq] f32
            pt = jnp.exp(st - lse_ref[0, h])
            if masked:
                pt = jnp.where(mask, pt, 0.0)
            dpt = _dot(_only(v_ref[0, :, kvlo:kvhi], kva, kvb), do, _NT)
            if delta_in:
                delta = delta_ref[0, h]
            else:
                delta = jnp.sum(pt * dpt, axis=0, keepdims=True)
            dst = (pt * (dpt - delta)).astype(dtype)
            if want_dkv:
                _put(dv_acc, kvlo, kvhi, kva, kvb,
                     _dot(pt.astype(dtype), do, _NN), add=True)
                _put(dk_acc, klo, khi, ka, kb, _dot(dst, q, _NN), add=True)
            if want_dq:
                # dq^T = K^T dS^T: the transpose falls on k, not on ds
                _put(dqt_acc, lo, hi, a, b, _dot(k, dst, _TN), axis=0,
                     add=True)

    _run_bodies(step, flags, bodies)

    if want_dq:
        @pl.when((flags & (1 if resident else 2)) != 0)
        def _dq_out():
            dq_ref[0] = (dqt_acc[:] * scale).T.astype(dq_ref.dtype)

    if want_dkv:
        @pl.when((flags & 2) != 0)
        def _dkv_out():
            dk_ref[0] = (dk_acc[:] * scale).astype(dk_ref.dtype)
            dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("causal", "block_q", "block_k",
                                    "interpret", "window"))
def flash_attention_bwd(q, k, v, out, lse, do, causal: bool = False,
                        block_q: Optional[int] = None,
                        block_k: Optional[int] = None,
                        interpret: bool = False,
                        window: Optional[int] = None):
    """Flash backward: (dq, dk, dv), each in its input's dtype (from
    float32 accumulators), without ever materializing the [L, L] score
    matrix — p is recomputed per block pair from the forward's logsumexp
    (the standard flash-attention backward; delta_i = rowsum(dO_i * O_i)
    folds the softmax normalizer's gradient).  v, out and do may have a
    head size of their own (Dv).  One kernel where the plan says so
    (`AttentionPlan.fused_backward`: one block pair is the whole
    sequence, and `out` is then not read, delta is rowsum(P dP) inside it;
    or a causal walk whose dq^T stays in VMEM), else a dq kernel and a
    dk/dv kernel.  With grouped heads dk and dv have k's and v's own
    heads: a kernel that makes them takes whole groups a step and sums
    over each."""
    B, Lq, H, D = q.shape
    Lk, Hkv, Dv = k.shape[1], k.shape[2], v.shape[-1]
    scale = 1.0 / float(np.sqrt(D))
    call = _prepare(q, k, v, causal, window, block_q, block_k)
    plan, pq, pk = call.plan, call.pad_q, call.pad_k
    bq, bk = plan.block_q, plan.block_k
    Lqp, Lkp = Lq + pq, Lk + pk
    delta_in = call.nq * call.nk > 1

    def rows(x):     # [B, H, Lq] f32 -> [B, H, 1, Lqp]
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pq))) if pq else x
        return x.reshape(B, H, 1, Lqp)

    slabs = lambda x, pad: _slabs(x.astype(call.dtype), pad)
    operands = [slabs(q, pq), slabs(k, pk), slabs(v, pk), slabs(do, pq),
                rows(lse)]
    if delta_in:
        # delta: [B, H, Lq] rows — one fused multiply-reduce over dO and O
        operands.append(rows(jnp.sum(
            do.astype(jnp.float32) * out.astype(jnp.float32),
            axis=-1).transpose(0, 2, 1)))
    dq_shape = jax.ShapeDtypeStruct((B, Lqp, H * D), q.dtype)
    dk_shape = jax.ShapeDtypeStruct((B, Lkp, Hkv * D), k.dtype)
    dv_shape = jax.ShapeDtypeStruct((B, Lkp, Hkv * Dv), v.dtype)

    def kernel_call(name, k_inner, want_dq, want_dkv):
        # dk and dv are sums over whole groups of query heads
        G = max(plan.heads, call.group) if want_dkv else plan.heads
        kv = _kv_heads(G, call.group)
        spec = call.specs(G)
        pairs, bodies = call.pairs(Lq, Lk, causal, k_inner)
        kernel = functools.partial(
            _bwd_kernel, scale=scale, heads=G, group=call.group, d=D, dv=Dv,
            bq=bq, bk=bk, q_len=Lq, kv_len=Lk, causal=causal,
            window=call.window, want_dq=want_dq, want_dkv=want_dkv,
            delta_in=delta_in, bodies=bodies)
        resident = want_dq and want_dkv
        dq_acc = pltpu.VMEM((call.nq,) * resident + (G * D, bq), jnp.float32)
        dkv_acc = [pltpu.VMEM((bk, kv * D), jnp.float32),
                   pltpu.VMEM((bk, kv * Dv), jnp.float32)]
        return pl.pallas_call(
            kernel,
            grid_spec=_grid_spec(
                pairs, (B, H // G),
                [spec.q, spec.k, spec.v, spec.o]
                + [spec.rows] * (len(operands) - 4),
                [spec.dq_of_run if resident else spec.q] * want_dq
                + [spec.k, spec.v] * want_dkv,
                [dq_acc] * want_dq + dkv_acc * want_dkv),
            out_shape=[dq_shape] * want_dq + [dk_shape, dv_shape] * want_dkv,
            compiler_params=dataclasses.replace(
                _SEMANTICS, vmem_limit_bytes=plan.vmem_bytes + _VMEM_HEADROOM)
            if resident else _SEMANTICS,
            interpret=interpret, name=name,
        )(*pairs, *operands)

    if plan.fused_backward:
        dq, dk, dv = kernel_call("flash_attention_bwd", False, True, True)
    else:
        dq, = kernel_call("flash_attention_bwd_dq", True, True, False)
        dk, dv = kernel_call("flash_attention_bwd_dkv", False, False, True)

    def back(x, L, heads):
        return x.reshape(B, x.shape[1], heads, -1)[:, :L]

    return back(dq, Lq, H), back(dk, Lk, Hkv), back(dv, Lk, Hkv)


def fused_attention_supported() -> bool:
    """True when the native kernel path is active (ops/dispatch.py: on a
    TPU)."""
    from geomx_tpu.ops.dispatch import kernel_mode
    return kernel_mode() == "native"


def _dense(q, k, v, causal, window=None):
    """f32-upcast dense attention.  Equal head counts and no window
    delegate the math to the numerical baseline
    (`full_attention_reference`), so the backward's gradients match it by
    construction; grouped heads (query head n reads key/value head
    n // group, no wider copy of k or v) and the causal band are the same
    form with the heads cut into groups and one more mask."""
    q32, k32, v32 = (x.astype(jnp.float32) for x in (q, k, v))
    B, L, H, D = q.shape
    Lk, Hkv = k.shape[1], k.shape[2]
    window = _band(window, causal, Lk)
    if H == Hkv and window is None:
        from geomx_tpu.parallel.ring_attention import \
            full_attention_reference
        return full_attention_reference(q32, k32, v32,
                                        causal=causal).astype(q.dtype)
    s = jnp.einsum("bqgrd,bkgd->bgrqk", q32.reshape(B, L, Hkv, H // Hkv, D),
                   k32) / jnp.sqrt(jnp.asarray(D, jnp.float32))
    if causal:
        back = jnp.arange(L)[:, None] - jnp.arange(Lk)[None, :]
        seen = back >= 0
        if window is not None:
            seen = seen & (back < window)
        s = jnp.where(seen, s, -jnp.inf)
    o = jnp.einsum("bgrqk,bkgd->bqgrd", jax.nn.softmax(s, axis=-1), v32)
    return o.reshape(B, L, H, v.shape[-1]).astype(q.dtype)


# the scope every instruction of attention's core sits under, kernels and
# the pads and reshapes around them alike (telemetry/layers.SCOPES;
# `attention_ms` reads it)
_SCOPE = "attn/core"


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def fused_attention(q, k, v, causal: bool = False,
                    interpret: bool = False,
                    window: Optional[int] = None):
    """Differentiable attention with platform dispatch built in: the
    Pallas kernels on TPU (or under ``interpret=True``), the dense jnp
    reference elsewhere — callers never gate on platform.  On the
    kernel path BOTH directions are flash: the backward recomputes p
    per tile from the forward's saved logsumexp, so the [L, L] score
    matrix never exists in HBM forward or backward.  k and v may have
    fewer heads than q (grouped-query heads: `Hq % Hkv == 0`, no wider
    copy is made on either path); ``window`` with ``causal``: query i sees
    key j iff 0 <= i - j < window."""
    with profile_scope(_SCOPE, "kernel"):
        if interpret or fused_attention_supported():
            return flash_attention(q, k, v, causal=causal,
                                   interpret=interpret, window=window)
        return _dense(q, k, v, causal, window)


def _fused_fwd(q, k, v, causal, interpret, window):
    with profile_scope(_SCOPE, "kernel"):
        if interpret or fused_attention_supported():
            out, lse = flash_attention_with_lse(q, k, v, causal=causal,
                                                interpret=interpret,
                                                window=window)
            return out, (q, k, v, out, lse)
        return _dense(q, k, v, causal, window), (q, k, v, None, None)


def _fused_bwd(causal, interpret, window, res, g):
    q, k, v, out, lse = res
    if lse is None:
        with profile_scope(_SCOPE, "kernel"):
            _, vjp = jax.vjp(
                lambda q_, k_, v_: _dense(q_, k_, v_, causal, window),
                q, k, v)
            return vjp(g)
    # kernel path: flash backward; the span says which form the plan took
    plan = _prepare(q, k, v, causal, window, None, None).plan
    with profile_scope(_SCOPE, "kernel", args={
            "backward_kernels": plan.backward_kernels,
            "resident_bytes": plan.resident_bytes}):
        return flash_attention_bwd(q, k, v, out, lse, g, causal=causal,
                                   interpret=interpret, window=window)


fused_attention.defvjp(_fused_fwd, _fused_bwd)
