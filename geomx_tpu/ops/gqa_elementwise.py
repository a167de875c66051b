"""The elementwise chains on either side of a gated grouped-query
attention core (`models/afmoe.GQAMixer`).

Between the q / k products and the attention kernels a mixer normalises
every head (RMSNorm over the head, statistics in float32, one learned
scale for q and one for k) and, in a window layer, turns it by its
position (rotate-half rotary over the whole head); behind the core it
multiplies the heads' output by the sigmoid of the gate's float32
logits.  Written as `decoder.RMSNorm`, `afmoe.rotary` and a multiply, XLA
runs them on a v5e as a dozen passes over float32 `[L, H, d]` arrays
(casts, the two halves' slices, a negation, two concatenates along the
minor dimension, four multiplies), forward, rematerialised and
transposed: 94 of `trinitymini-fsa-1c`'s 202 ms under ``gqa/proj``
(PERF.md, PR 33).  Here:

- :func:`norm_rotary` ``(q, k, q_scale, k_scale, eps, rope)``: the norm
  and rotary of q ``[B, L, H, d]`` and k ``[B, L, KV, d]`` as ONE streamed
  pass, a Pallas kernel pair that reads and writes the caller's dtype.
  With ``d`` a multiple of 128 a head is whole lane tiles: its mean is a
  lane reduction, and rotate-half is a lane roll by ``d / 2`` times a
  sine table whose first half is negated (``concat(-x2, x1) * sin`` equals
  ``roll(x) * concat(-sin, sin)`` to the bit): no slice, no negation, no
  concatenate.  The float32 tables ``[L, d]`` are XLA's
  (:func:`rotary_tables`: ``rope`` a theta, or a :class:`Yarn`, whose
  blended frequencies and factor on cos and sin are only another way of
  filling them) and read a token tile at a time.  A bf16 caller
  keeps the rounding to bf16 between norm and rotary that the chain's
  code has (XLA's TPU program of the chain elides that round trip, so
  against it the kernel's q' and k' differ in the last place of bf16 in a
  third of the elements: PERF.md, PR 37).
  The backward pass, written by hand, saves q and k as they came and
  nothing in float32, recomputes the normalised value, and returns dq, dk
  and the two scales' gradients (float32, summed over tokens and heads
  in an accumulator the grid's steps share).
- :func:`norm_rotary_ref` and :func:`gated_ref`: the jnp forms (JAX's own
  backward).  The first shares the kernels' arithmetic function for
  function: it is the only path off a TPU, at head sizes that are not
  whole lane tiles and for the norm alone (``rope`` None: a layer with
  no positions), and the oracle `tests/test_gqa_elementwise.py` holds the
  kernels to.  The second, ``(o * sigmoid(logits)).astype(o.dtype)``, is
  the gate everywhere.

The kernels see ``[B, L, H * d]``: heads side by side, which is how the
products emit them and how `ops/flash_attention.py` reads them, so no
relayout stands at either door.  A grid step takes a tile of tokens
(:func:`norm_rotary_plan`: what fits `VMEM_BUDGET`, a power of two up to
`MAX_TILE`; a last tile may be ragged) and walks the heads, a head of the
whole tile at a time, so that no float32 intermediate reaches HBM.

`ops/dispatch.gqa_norm_rotary` chooses between kernel and jnp form;
`tools/gqa_proj_timing.py` times both against the chain they replaced
(ROADMAP D3).  Pairs for the norm alone and for the gate were written
and timed too, did not beat XLA's code on the jnp forms, and went
(PERF.md, PR 37).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# what a grid step's pipelined blocks may take of VMEM, and the most and
# the fewest tokens of a tile.  A head of the whole tile is one operation
# of the kernels: at 32 tokens a time the same work took 2.6 times as
# long (PERF.md, PR 37), and a bf16 vector register holds 16 rows.
VMEM_BUDGET = 24 * 2 ** 20
MAX_TILE = 256
MIN_TILE = 16

_F32 = jnp.float32


class Yarn(NamedTuple):
    """YaRN's positions as a `rope_parameters` entry of `rope_type`
    ``"yarn"`` gives them: frequencies that turn more than ``beta_fast``
    times within ``original`` positions stay, those that turn fewer than
    ``beta_slow`` times are divided by ``factor``, a linear ramp between;
    cos and sin times ``attention_factor``."""
    theta: float
    factor: float
    original: int
    beta_fast: float
    beta_slow: float
    attention_factor: float


def yarn_correction_range(d: int, rope: Yarn):
    """``(low, high)``: the pairs of a head of ``d`` between which YaRN's
    ramp runs, the pair that turns ``beta_fast`` times within ``original``
    positions rounded down and the one that turns ``beta_slow`` times
    rounded up, inside [0, d - 1] (18 and 35 at 128, theta 500,000,
    8,192 positions, 32 and 1)."""
    pair = lambda turns: (d * math.log(rope.original / (turns * 2 * math.pi))
                          / (2 * math.log(rope.theta)))
    low = max(math.floor(pair(rope.beta_fast)), 0)
    high = min(math.ceil(pair(rope.beta_slow)), d - 1)
    return low, high


def rotary_frequencies(d: int, rope):
    """``(inverse frequencies [d / 2] float32, factor on cos and sin)`` of
    the positions ``rope`` names: a float is rotary's theta
    (``theta^(-2 i / d)``, factor 1), a :class:`Yarn` its blend of those
    and those over ``factor``."""
    half = d // 2
    theta = rope.theta if isinstance(rope, Yarn) else rope
    inverse = theta ** (-jnp.arange(half, dtype=_F32) * 2.0 / d)
    if not isinstance(rope, Yarn):
        return inverse, 1.0
    low, high = yarn_correction_range(d, rope)
    ramp = jnp.clip((jnp.arange(half, dtype=_F32) - low)
                    / (high - low if high > low else 0.001), 0.0, 1.0)
    return (inverse / rope.factor * ramp + inverse * (1.0 - ramp),
            rope.attention_factor)


def rotary_tables(length: int, d: int, rope):
    """``(cos, signed sin)`` of rotate-half rotary over a head of ``d`` at
    positions 0..length-1 under ``rope`` (:func:`rotary_frequencies`),
    float32 ``[length, d]``: both halves of a head share an angle, and the
    first half's sine is negated so that
    ``x * cos + roll(x, d / 2) * sin`` is the rotation."""
    inverse, factor = rotary_frequencies(d, rope)
    angle = jnp.arange(length, dtype=_F32)[:, None] * inverse[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    if factor != 1.0:
        cos, sin = factor * cos, factor * sin
    return (jnp.concatenate([cos, cos], -1), jnp.concatenate([-sin, sin], -1))


def _roll_half(x):
    return jnp.roll(x, x.shape[-1] // 2, -1)


def _lane_roll_half(x):
    return pltpu.roll(x, x.shape[-1] // 2, x.ndim - 1)


def _mean(x):
    return jnp.mean(x, -1, keepdims=True)


def _norm_turn(x, scale, tables, eps, roll):
    """RMSNorm over the last axis (float32, the learned scale applied,
    rounded to x's dtype as `decoder.RMSNorm` does), then rotary where
    ``tables`` (cos, signed sin; broadcastable to x) are given."""
    x32 = x.astype(_F32)
    y = (x32 * lax.rsqrt(_mean(jnp.square(x32)) + eps) * scale).astype(
        x.dtype)
    if tables is None:
        return y
    cos, sin = tables
    y32 = y.astype(_F32)
    return (y32 * cos + roll(y32) * sin).astype(x.dtype)


def _norm_turn_bwd(x, scale, tables, eps, g, roll):
    """:func:`_norm_turn`'s cotangents with rotary, from its input and
    ``g``: ``dx`` in x's dtype and the scale's gradient before its sum
    over tokens and heads (float32, x's shape).  A roll by half the axis
    is its own transpose; the rounding between norm and rotary passes
    ``g`` on."""
    x32, g32 = x.astype(_F32), g.astype(_F32)
    r = lax.rsqrt(_mean(jnp.square(x32)) + eps)
    n = x32 * r
    cos, sin = tables
    dy = g32 * cos + roll(g32 * sin)
    dn = dy * scale
    dx = (dn - n * _mean(dn * n)) * r
    return dx.astype(x.dtype), dy * n


def norm_rotary_ref(q, k, q_scale, k_scale, eps: float, rope):
    """The jnp form of :func:`norm_rotary`: any head size, the norm alone
    where ``rope`` is None, JAX's backward."""
    tables = None
    if rope is not None:
        tables = tuple(t[None, :, None, :] for t in
                       rotary_tables(q.shape[1], q.shape[-1], rope))
    return (_norm_turn(q, q_scale, tables, eps, _roll_half),
            _norm_turn(k, k_scale, tables, eps, _roll_half))


def gated_ref(o, logits):
    """``(o * sigmoid(logits)).astype(o.dtype)``: a mixer's output under
    its gate's float32 logits."""
    return (o.astype(_F32) * jax.nn.sigmoid(logits)).astype(o.dtype)


# ----------------------------------------------------------------- the plan

class Plan(NamedTuple):
    """The kernels' token tile and what a grid step's pipelined blocks
    take of VMEM (every operand and result, each held twice)."""
    tile: int
    vmem_bytes: int


def norm_rotary_plan(q_shape, k_shape, dtype,
                     backward: bool = True) -> Optional[Plan]:
    """The plan of :func:`norm_rotary`'s kernels for q ``[B, L, H, d]``
    and k ``[B, L, KV, d]`` of ``dtype`` (the backward's by default: it
    holds three arrays a head where the forward holds two): the largest
    power of two of tokens up to `MAX_TILE` and up to ``L`` whose blocks
    fit `VMEM_BUDGET`.  None where the kernels do not apply: a head that
    is not whole lane tiles, another dtype than bf16 or float32, fewer
    tokens than `MIN_TILE`."""
    (_, length, heads, d), kv_heads = q_shape, k_shape[2]
    if d % LANES or jnp.dtype(dtype) not in (jnp.dtype(jnp.bfloat16),
                                             jnp.dtype(_F32)):
        return None
    token_bytes = ((3 if backward else 2) * (heads + kv_heads) * d
                   * jnp.dtype(dtype).itemsize + 2 * d * 4)
    fits = lambda tile: (tile <= length
                         and 2 * tile * token_bytes <= VMEM_BUDGET)
    tile = MAX_TILE
    while tile > MIN_TILE and not fits(tile):
        tile //= 2
    return Plan(tile, 2 * tile * token_bytes) if fits(tile) else None


# -------------------------------------------------------------- the kernels

def _norm_rotary_fwd_kernel(q_ref, k_ref, q_scale_ref, k_scale_ref, cos_ref,
                            sin_ref, qo_ref, ko_ref, *, heads, d, eps):
    tables = cos_ref[...], sin_ref[...]
    for src, dst, scale, count in ((q_ref, qo_ref, q_scale_ref[...], heads[0]),
                                   (k_ref, ko_ref, k_scale_ref[...], heads[1])):
        for h in range(count):
            at = slice(h * d, (h + 1) * d)
            dst[0, :, at] = _norm_turn(src[0, :, at], scale, tables, eps,
                                       _lane_roll_half)


def _norm_rotary_bwd_kernel(q_ref, k_ref, gq_ref, gk_ref, q_scale_ref,
                            k_scale_ref, cos_ref, sin_ref, dq_ref, dk_ref,
                            dq_scale_ref, dk_scale_ref, *, heads, d, eps,
                            tile, length):
    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _init():
        dq_scale_ref[...] = jnp.zeros_like(dq_scale_ref)
        dk_scale_ref[...] = jnp.zeros_like(dk_scale_ref)

    tables = cos_ref[...], sin_ref[...]
    ragged = length % tile != 0     # the last tile has rows past the end
    if ragged:
        real = pl.program_id(1) * tile + lax.broadcasted_iota(
            jnp.int32, (tile, 1), 0) < length
    for src, g_ref, dst, scale, sum_ref, count in (
            (q_ref, gq_ref, dq_ref, q_scale_ref[...], dq_scale_ref, heads[0]),
            (k_ref, gk_ref, dk_ref, k_scale_ref[...], dk_scale_ref, heads[1])):
        total = jnp.zeros((tile, d), _F32)
        for h in range(count):
            at = slice(h * d, (h + 1) * d)
            dst[0, :, at], ds = _norm_turn_bwd(
                src[0, :, at], scale, tables, eps, g_ref[0, :, at],
                _lane_roll_half)
            total = total + (jnp.where(real, ds, 0.0) if ragged else ds)
        sum_ref[...] += jnp.sum(total, 0, keepdims=True)


def _norm_rotary_call(q, k, q_scale, k_scale, cotangents, eps, rope,
                      interpret):
    """One kernel of the pair on q, k ``[B, L, heads, d]``: the forward
    (``cotangents`` empty: q', k') or the backward (``(gq, gk)``: dq, dk
    and the scales' ``[1, d]`` float32 sums), every wide array seen as
    ``[B, L, heads * d]``."""
    b, length, h, d = q.shape
    heads, backward = (h, k.shape[2]), bool(cotangents)
    if k.shape != (b, length, heads[1], d) or k.dtype != q.dtype:
        raise ValueError(f"q {q.shape} {q.dtype} against k {k.shape} "
                         f"{k.dtype}")
    plan = norm_rotary_plan(q.shape, k.shape, q.dtype, backward)
    if plan is None:
        raise ValueError(f"no kernel for q {q.shape} {q.dtype}")
    wide = [pl.BlockSpec((1, plan.tile, n * d), lambda b, i: (b, i, 0))
            for n in heads]
    scale = pl.BlockSpec((1, d), lambda b, i: (0, 0))
    table = pl.BlockSpec((plan.tile, d), lambda b, i: (i, 0))
    flat = [x.reshape(b, length, -1) for x in (q, k, *cotangents)]
    if backward:
        kernel = functools.partial(_norm_rotary_bwd_kernel, heads=heads, d=d,
                                   eps=eps, tile=plan.tile, length=length)
    else:
        kernel = functools.partial(_norm_rotary_fwd_kernel, heads=heads, d=d,
                                   eps=eps)
    return pl.pallas_call(
        kernel, grid=(b, pl.cdiv(length, plan.tile)),
        in_specs=wide * (2 if backward else 1) + [scale] * 2 + [table] * 2,
        out_specs=wide + [scale] * (2 * backward),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in flat[:2]]
        + [jax.ShapeDtypeStruct((1, d), _F32)] * (2 * backward),
        compiler_params=pltpu.CompilerParams(
            # the backward's sums live in one block that every step adds to
            dimension_semantics=("arbitrary" if backward else "parallel",) * 2,
            vmem_limit_bytes=plan.vmem_bytes + 16 * 2 ** 20),
        name="gqa_norm_rotary_bwd" if backward else "gqa_norm_rotary_fwd",
        interpret=interpret,
    )(*flat, q_scale.astype(_F32).reshape(1, d),
      k_scale.astype(_F32).reshape(1, d), *rotary_tables(length, d, rope))


# Each kernel's call sits in a module-level jit with its statics named, so
# that a step's four window layers share one trace of each body.

@functools.partial(jax.jit, static_argnames=("eps", "rope", "interpret"))
def norm_rotary_fwd(q, k, q_scale, k_scale, *, eps: float, rope,
                    interpret: bool = False):
    """The forward kernel: normalised and turned q and k in their own
    dtype and shape."""
    qo, ko = _norm_rotary_call(q, k, q_scale, k_scale, (), eps, rope,
                               interpret)
    return qo.reshape(q.shape), ko.reshape(k.shape)


@functools.partial(jax.jit, static_argnames=("eps", "rope", "interpret"))
def norm_rotary_bwd(q, k, q_scale, k_scale, gq, gk, *, eps: float,
                    rope, interpret: bool = False):
    """The backward kernel, from the forward's inputs and its results'
    cotangents: ``(dq, dk, dq_scale, dk_scale)``, the scales' in float32
    ``[d]``."""
    dq, dk, dq_scale, dk_scale = _norm_rotary_call(
        q, k, q_scale, k_scale, (gq, gk), eps, rope, interpret)
    return (dq.reshape(q.shape), dk.reshape(k.shape), dq_scale.reshape(-1),
            dk_scale.reshape(-1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def norm_rotary(q, k, q_scale, k_scale, eps: float, rope,
                interpret: bool = False):
    """Per-head RMSNorm of q ``[B, L, H, d]`` and k ``[B, L, KV, d]``, then
    rotate-half rotary at positions 0..L-1 under ``rope`` (a theta or a
    :class:`Yarn`, hashable: the jits' static argument), through the
    kernel pair (`norm_rotary_plan` says where it applies)."""
    return norm_rotary_fwd(q, k, q_scale, k_scale, eps=eps, rope=rope,
                           interpret=interpret)


def _norm_rotary_vjp_fwd(q, k, q_scale, k_scale, eps, rope, interpret):
    out = norm_rotary_fwd(q, k, q_scale, k_scale, eps=eps, rope=rope,
                          interpret=interpret)
    return out, (q, k, q_scale, k_scale)


def _norm_rotary_vjp_bwd(eps, rope, interpret, saved, g):
    q, k, q_scale, k_scale = saved
    dq, dk, dq_scale, dk_scale = norm_rotary_bwd(
        q, k, q_scale, k_scale, *g, eps=eps, rope=rope, interpret=interpret)
    return (dq, dk, dq_scale.astype(q_scale.dtype).reshape(q_scale.shape),
            dk_scale.astype(k_scale.dtype).reshape(k_scale.shape))


norm_rotary.defvjp(_norm_rotary_vjp_fwd, _norm_rotary_vjp_bwd)
