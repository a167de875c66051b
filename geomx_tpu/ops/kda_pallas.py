"""The chunked gated delta rule (`ops/kda.py`) as a pair of Pallas
kernels: a forward sweep over the chunks with the state in VMEM, and a
reverse sweep with the state's cotangent in VMEM.

**Schedule** (docs/kernels.md has the long form).  The grid is (sequence,
head group, run of chunks), the last axis sequential.  A step holds
``heads`` heads and ``chunks`` chunks of q, k, v, g, beta in the
heads-major layout the mixer writes (`[B, H, L, d]`: a chunk is a run of
whole tiles) and walks its chunks in a `fori_loop` whose body does every
head of the step, ``128 / chunk`` heads stacked into one chunk
computation (a pack: 128 rows fill the matrix unit where one head's 64
fill a quarter; the heads' rows never pair), the packs' products
interleaved.  Per chunk everything `kda_chunked` computes happens in
VMEM: the decays, the two score matrices,
``T = (I + Diag(beta) A_kk)^-1 Diag(beta)``, the pseudo-values, the
output and the hand-over.  HBM sees q, k, v, g, beta once, ``o`` once
and, where a backward follows, each chunk's starting state (64 KB a chunk
and head at 128 x 128: half of what the chunk's inputs are).
:func:`kda_plan` picks heads and chunks a step from the shapes.

**Decay without a positive exponent.**  A score needs
``exp(G_i - G_j)`` channel by channel for j <= i.  The chunk is halved
recursively: at the level of half-size ``h`` the pairs (i in the second
half of its 2h-block, j in the first) factor through the boundary
between the halves, ``exp(G_i - G_r) exp(G_r - G_j)`` with both
exponents <= 0, so one product of the whole chunk's scaled rows against
its scaled columns, masked to those pairs, gives them all; log2(chunk)
levels and the diagonal (no decay) make the matrix.  The exponents of all
levels come from one doubling pass over ``g`` on the vector unit
(prefix sums and totals within aligned blocks, float32, sublane rolls),
which ends in the chunk's cumulative log-decay; its transpose, run
backwards, turns the exponents' cotangents into ``dg``.  Nothing is ever
divided by a decay and no difference above zero is exponentiated.

**Precision** is `kda_chunked`'s: the operands of the large products are
``dtype`` with float32 accumulation; levels below ``sub`` tokens (the
jnp form's float32 diagonal sub-blocks), the triangular inverse and its
cotangent are float32 products at `HIGHEST` whatever ``dtype`` is
(:func:`_dot` with ``exact``); decays, state and every accumulator are
float32.

**Backward.**  A reverse sweep: a step reads its chunks' inputs, saved
starting states and ``do``, recomputes the chunk's internals, and carries
``dS`` from the later chunk.  The inverse's cotangent is the closed form
``dM = -X^T dT T^T`` (two products), not the transpose of its
construction.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from geomx_tpu.utils.profiler import profile_scope

_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))
_F32 = jnp.float32

# rows of one head a grid step aims to hold (chunks x chunk), the most
# heads a step (bounds the unrolled code), and what the blocks may take
# of VMEM (double-buffered by Pallas's pipeline)
STEP_ROWS = 256
MAX_HEADS = 4
VMEM_BUDGET = 24 * 2 ** 20
_SCOPE = "kda/scan"


class KDAPlan(NamedTuple):
    """What one grid step holds (:func:`kda_plan`)."""
    heads: int          # heads a step, a divisor of H
    chunks: int         # chunks a step
    vmem_bytes: int     # the backward kernel's blocks, double-buffered


def _block_bytes(heads, chunks, chunk, dk, dv, itemsize):
    """Double-buffered VMEM blocks of the backward kernel, the larger of
    the two: q, k, g and their cotangents in float32, v and dv in the
    operands' dtype, do, beta and dbeta, the saved states."""
    rows = heads * chunks * chunk
    io = rows * (6 * dk * 4 + 2 * dv * itemsize + dv * 4)
    beta = 2 * heads * chunks * 8 * max(chunk, 128) * 4
    states = heads * chunks * dv * dk * 4
    return 2 * (io + beta + states)


def kda_plan(length: int, heads: int, dk: int, dv: int, chunk: int,
             dtype) -> KDAPlan:
    """Heads and chunks a grid step, from the shapes alone: as many
    chunks as make STEP_ROWS rows (at most the sequence's), and the
    largest divisor of H up to MAX_HEADS whose blocks fit VMEM_BUDGET."""
    n = -(-length // chunk)
    chunks = max(1, min(n, STEP_ROWS // chunk))
    itemsize = jnp.dtype(dtype).itemsize
    cost = lambda g: _block_bytes(g, chunks, chunk, dk, dv, itemsize)
    fits = [g for g in range(1, min(heads, MAX_HEADS) + 1)
            if heads % g == 0 and cost(g) <= VMEM_BUDGET]
    group = max(fits) if fits else 1
    return KDAPlan(group, chunks, cost(group))


# ---- one chunk of a pack of heads, on values in VMEM ------------------------
#
# A pack is `pack` heads whose chunks are stacked along the rows: R =
# pack x chunk rows (128 at a chunk of 64) that fill the matrix unit where
# one head's 64 would fill a quarter of it.  Heads never meet: every mask
# of pairs below asks for the same aligned block of at most `chunk` rows,
# so the [R, R] matrices are block-diagonal and the inverse of the stack is
# the stack of the inverses.  Only the state is a head's own.

def _dot(a, b, dims, dtype, exact=False):
    """Float32 result of one product on the matrix unit.  Operands are
    rounded to ``dtype``; where ``exact`` they stay float32 and the
    product runs at `HIGHEST`, for every caller (a float32 product at the
    default precision rounds its operands to bf16 on the chip)."""
    if exact:
        return lax.dot_general(a.astype(_F32), b.astype(_F32), dims,
                               precision=lax.Precision.HIGHEST,
                               preferred_element_type=_F32)
    return lax.dot_general(a.astype(dtype), b.astype(dtype), dims,
                           preferred_element_type=_F32)


def _halvings(c):
    return [1 << b for b in range(c.bit_length() - 1)]     # 1, 2, .., c/2


class _Masks(NamedTuple):
    """Index masks of a pack's chunk, made once a grid step."""
    chunk: int
    first: jax.Array        # [R, dk] bool: a head's first token
    eye: jax.Array          # [R, R] bool
    below: jax.Array        # [R, R] bool: j < i in one head
    level: tuple            # per halving, [2R, R] bool: the level's pairs
    second: tuple           # per halving, [R, dk] bool: the later half
    same: dict              # block size -> [R, R] bool: one aligned block


def _masks(c, pack, dk):
    rows = c * pack
    token = lax.broadcasted_iota(jnp.int32, (rows, dk), 0)
    row = lax.broadcasted_iota(jnp.int32, (rows, rows), 0)
    col = lax.broadcasted_iota(jnp.int32, (rows, rows), 1)
    same = {2 * h: (row ^ col) < 2 * h for h in _halvings(c)}
    level = []
    for h in _halvings(c):
        pairs = same[2 * h] & ((row & h) != 0) & ((col & h) == 0)
        level.append(jnp.concatenate([pairs, pairs], axis=0))
    second = tuple((token & h) != 0 for h in _halvings(c))
    return _Masks(c, (token & (c - 1)) == 0, row == col,
                  same[c] & (col < row), tuple(level), second, same)


def _swap(x, second, h):
    """Each row's partner in the other half of its 2h-block."""
    return jnp.where(second, pltpu.roll(x, h, 0),
                     pltpu.roll(x, x.shape[0] - h, 0))


def _decays(g, masks):
    """g [R, dk] float32 (<= 0).  Returns per halving h the exponent
    (<= 0) each token's row takes at that level: a token in the second
    half of its 2h-block the log-decay from the boundary between the
    halves to itself, one in the first half from itself to the boundary;
    then the inclusive cumulative log-decay and the chunk's total
    (every row of a head the same).  One doubling pass: `p` the inclusive
    prefix sum and `t` the total within aligned h-blocks."""
    p = t = g
    levels = []
    for h, second in zip(_halvings(masks.chunk), masks.second):
        levels.append(jnp.minimum(jnp.where(second, p, t - p), 0.0))
        other = _swap(t, second, h)
        p = p + jnp.where(second, other, 0.0)
        t = t + other
    return levels, p, t


def _decays_transpose(d_levels, d_p, d_t, masks):
    """Cotangents of :func:`_decays`' outputs -> dg (the clamp at zero,
    which only roundings reach, counts as the identity)."""
    for h, second, d_e in reversed(list(zip(
            _halvings(masks.chunk), masks.second, d_levels))):
        d_other = d_t + jnp.where(second, d_p, 0.0)
        d_t = d_t + _swap(d_other, second, h) + jnp.where(second, 0.0, d_e)
        d_p = d_p + jnp.where(second, d_e, -d_e)
    return d_p + d_t


def _scores(q, k, levels, masks, sub, dtype):
    """[2R, R] float32: rows [:R] A_qk below the diagonal, rows [R:] A_kk;
    and each level's scaled operands and scale for the backward."""
    rows = q.shape[0]
    a = jnp.zeros((2 * rows, rows), _F32)
    kept = []
    for h, e, pairs in zip(_halvings(masks.chunk), levels, masks.level):
        f = jnp.exp(e)
        kf = k * f
        lhs = jnp.concatenate([q * f, kf], axis=0)
        a = jnp.where(pairs, _dot(lhs, kf, _NT, dtype, h < sub), a)
        kept.append((lhs, kf, f))
    return a, kept


def _unit_lower_inverse(m, masks, dtype):
    """(I + m)^-1 for strictly lower-triangular blocks m [R, R], as
    `kda.unit_lower_inverse`: blocks of 16 by the finite Neumann product,
    larger ones by the 2x2 block formula; on whole [R, R] matrices, the
    blocks kept apart by masks."""
    mm = lambda a, b: _dot(a, b, _NN, dtype, exact=True)
    base = min(16, masks.chunk)
    eye = masks.eye.astype(_F32)
    md = jnp.where(masks.same[base], m, 0.0)
    out, reach = eye - md, 2
    power = mm(md, md) if base > 2 else md
    while reach < base:
        out = mm(out, eye + power)
        reach *= 2
        if reach < base:
            power = mm(power, power)
    size = base
    while size < masks.chunk:
        low = jnp.where(masks.same[2 * size] & ~masks.same[size], m, 0.0)
        out = out - mm(mm(out, low), out)
        size *= 2
    return out


def _to_col(row_vec, eye):
    """[1, R] -> [R, 1] without a transpose."""
    return jnp.sum(jnp.where(eye, row_vec, 0.0), axis=1, keepdims=True)


def _to_row(col_vec, eye):
    return jnp.sum(jnp.where(eye, col_vec, 0.0), axis=0, keepdims=True)


def _per_head(x, c):
    return [x[i:i + c] for i in range(0, x.shape[0], c)]


def _stack(xs):
    return xs[0] if len(xs) == 1 else jnp.concatenate(xs, axis=0)


class _Chunk(NamedTuple):
    """A pack's chunk, as the backward needs it."""
    o: jax.Array
    states: tuple           # the heads' states after the chunk, [dv, dk]
    kept: list
    a_qk: jax.Array
    a_kk: jax.Array
    beta_col: jax.Array
    x: jax.Array
    t: jax.Array
    ei: jax.Array
    eo: jax.Array
    keep: list
    q_in: jax.Array
    k_in: jax.Array
    k_out: jax.Array
    w_k: jax.Array
    u: jax.Array


def _chunk(q, k, v, g, beta, states, masks, sub, dtype) -> _Chunk:
    """q, k, g [R, dk] float32, v [R, dv], beta [1, R]; `states` a head's
    [dv, dk] float32 each (the state transposed: the decay to the chunk's
    end is then a row that scales its lanes)."""
    rows, c, dv = q.shape[0], masks.chunk, v.shape[1]
    dot = functools.partial(_dot, dtype=dtype)
    levels, g_cum, g_end = _decays(g, masks)
    a, kept = _scores(q, k, levels, masks, sub, dtype)
    a_qk = jnp.where(masks.eye, jnp.sum(q * k, axis=1, keepdims=True),
                     a[:rows])
    a_kk = a[rows:]
    beta_col = _to_col(beta, masks.eye)
    x = _unit_lower_inverse(beta_col * a_kk, masks, dtype)
    t = x * beta
    ei, eo = jnp.exp(g_cum), jnp.exp(g_end - g_cum)
    keep = [jnp.exp(e[:1]) for e in _per_head(g_end, c)]
    q_in, k_in, k_out = q * ei, k * ei, k * eo
    w = dot(t, jnp.concatenate([v.astype(dtype), k_in.astype(dtype)], 1),
            _NN)
    w_k = w[:, dv:]
    r = [dot(jnp.concatenate([w_k_, q_in_], 0), state, _NT)
         for w_k_, q_in_, state in zip(_per_head(w_k, c),
                                       _per_head(q_in, c), states)]
    u = w[:, :dv] - _stack([r_[:c] for r_ in r])
    o = _stack([r_[c:] for r_ in r]) + dot(a_qk, u, _NN)
    new = tuple(keep_ * state + dot(u_, k_out_, _TN)
                for keep_, state, u_, k_out_ in zip(
                    keep, states, _per_head(u, c), _per_head(k_out, c)))
    return _Chunk(o, new, kept, a_qk, a_kk, beta_col, x, t, ei, eo, keep,
                  q_in, k_in, k_out, w_k, u)


def _chunk_backward(q, k, v, g, beta, states, do, d_states, masks, sub,
                    dtype):
    """Cotangents (dq, dk, dv, dg, dbeta [1, R], the heads' d_state at the
    chunk's start) from ``do`` [R, dv] and the cotangents of the states
    after the chunk."""
    rows, c, dv = q.shape[0], masks.chunk, v.shape[1]
    f = _chunk(q, k, v, g, beta, states, masks, sub, dtype)
    dot = functools.partial(_dot, dtype=dtype)
    heads = lambda x: _per_head(x, c)
    du = dot(f.a_qk, do, _TN) + _stack(
        [dot(k_out, d_state, _NT)
         for k_out, d_state in zip(heads(f.k_out), d_states)])
    da_qk = dot(do, f.u, _NT)
    dk_out = _stack([dot(u, d_state, _NN)
                     for u, d_state in zip(heads(f.u), d_states)])
    # a head's [do; -du]: against its state dq_in over dw_k, against
    # [q_in; w_k] the state's cotangent
    both = [jnp.concatenate([do_, -du_], 0)
            for do_, du_ in zip(heads(do), heads(du))]
    back = [dot(b, state, _NN) for b, state in zip(both, states)]
    dq_in = _stack([b[:c] for b in back])
    dw_k = _stack([b[c:] for b in back])
    d_keep = [jnp.sum(d_state * state, axis=0, keepdims=True)
              for d_state, state in zip(d_states, states)]
    d_start = tuple(
        keep * d_state + dot(b, jnp.concatenate([q_in, w_k], 0), _TN)
        for keep, d_state, b, q_in, w_k in zip(
            f.keep, d_states, both, heads(f.q_in), heads(f.w_k)))
    duk = jnp.concatenate([du.astype(dtype), dw_k.astype(dtype)], 1)
    dt = jnp.where(masks.same[c], dot(duk, jnp.concatenate(
        [v.astype(dtype), f.k_in.astype(dtype)], 1), _NT), 0.0)
    dvk = dot(f.t, duk, _TN)
    dk_in = dvk[:, dv:]
    dbeta = jnp.sum(f.x * dt, axis=0, keepdims=True)
    dm = -dot(dot(f.x, dt, _TN, exact=True), f.t, _NT, exact=True)
    dm = jnp.where(masks.below, dm, 0.0)
    dbeta = dbeta + _to_row(jnp.sum(dm * f.a_kk, axis=1, keepdims=True),
                            masks.eye)
    d_diag = jnp.sum(jnp.where(masks.eye, da_qk, 0.0), axis=1, keepdims=True)
    d_a = jnp.concatenate([da_qk, f.beta_col * dm], axis=0)
    dq = d_diag * k + dq_in * f.ei
    dk = d_diag * q + dk_in * f.ei + dk_out * f.eo
    d_levels = []
    for h, (lhs, kf, scale), pairs in zip(_halvings(c), f.kept, masks.level):
        d_pairs = jnp.where(pairs, d_a, 0.0)
        d_lhs = dot(d_pairs, kf, _NN, exact=h < sub)
        d_kf = d_lhs[rows:] + dot(d_pairs, lhs, _TN, exact=h < sub)
        dq = dq + d_lhs[:rows] * scale
        dk = dk + d_kf * scale
        d_levels.append(lhs[:rows] * d_lhs[:rows] + kf * d_kf)
    d_out = dk_out * f.k_out
    d_p = dq_in * f.q_in + dk_in * f.k_in - d_out
    d_end = _stack([jnp.broadcast_to(d_keep_ * keep, (c, keep.shape[1]))
                    for d_keep_, keep in zip(d_keep, f.keep)])
    d_t = d_out + jnp.where(masks.first, d_end, 0.0)
    dg = _decays_transpose(d_levels, d_p, d_t, masks)
    return dq, dk, dvk[:, :dv], dg, dbeta, d_start


# ---- the kernels -----------------------------------------------------------

def _rows(n, chunk):
    return pl.ds(pl.multiple_of(n * chunk, chunk), chunk)


def _pack(heads, chunk):
    """Heads stacked into one chunk computation: as many as make 128 rows,
    a divisor of the step's heads."""
    want = max(1, 128 // chunk)
    return max(p for p in range(1, heads + 1)
               if heads % p == 0 and p <= want)


def _load(ref, packed, rows, dtype=_F32):
    """The chunk's rows of a pack's heads, stacked."""
    return _stack([ref[0, h, rows, :].astype(dtype) for h in packed])


def _load_beta(beta_ref, packed, n):
    """The chunk's beta of a pack's heads, side by side: [1, R]."""
    return jnp.concatenate([beta_ref[0, h, n].astype(_F32) for h in packed],
                           axis=1)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, *refs, heads,
                chunks, chunk, sub, dtype, save):
    states_ref = refs[0] if save else None
    state_ref = refs[-1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    pack = _pack(heads, chunk)
    masks = _masks(chunk, pack, q_ref.shape[-1])

    def one_chunk(n, states):
        rows = _rows(n, chunk)
        out = []
        for first in range(0, heads, pack):
            packed = range(first, first + pack)
            mine = states[first:first + pack]
            if save:
                for h, state in zip(packed, mine):
                    states_ref[0, h, n] = state
            f = _chunk(_load(q_ref, packed, rows), _load(k_ref, packed, rows),
                       _load(v_ref, packed, rows, v_ref.dtype),
                       _load(g_ref, packed, rows),
                       _load_beta(beta_ref, packed, n), mine, masks, sub,
                       dtype)
            for i, h in enumerate(packed):
                o_ref[0, h, rows, :] = f.o[i * chunk:(i + 1) * chunk]
            out.extend(f.states)
        return tuple(out)

    states = lax.fori_loop(0, chunks, one_chunk,
                           tuple(state_ref[h] for h in range(heads)))
    for h, state in enumerate(states):
        state_ref[h] = state


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, states_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, d_state_ref, *,
                heads, chunks, chunk, sub, dtype):
    @pl.when(pl.program_id(2) == 0)
    def _():
        d_state_ref[...] = jnp.zeros_like(d_state_ref)

    pack = _pack(heads, chunk)
    masks = _masks(chunk, pack, q_ref.shape[-1])

    def one_chunk(i, d_states):
        n = chunks - 1 - i
        rows = _rows(n, chunk)
        out = []
        for first in range(0, heads, pack):
            packed = range(first, first + pack)
            dq, dk, dv, dg, dbeta, d_start = _chunk_backward(
                _load(q_ref, packed, rows), _load(k_ref, packed, rows),
                _load(v_ref, packed, rows, v_ref.dtype),
                _load(g_ref, packed, rows),
                _load_beta(beta_ref, packed, n),
                tuple(states_ref[0, h, n] for h in packed),
                _load(do_ref, packed, rows),
                d_states[first:first + pack], masks, sub, dtype)
            for j, h in enumerate(packed):
                part = slice(j * chunk, (j + 1) * chunk)
                dq_ref[0, h, rows, :] = dq[part]
                dk_ref[0, h, rows, :] = dk[part]
                dv_ref[0, h, rows, :] = dv[part].astype(dv_ref.dtype)
                dg_ref[0, h, rows, :] = dg[part]
                dbeta_ref[0, h, n] = dbeta[:, part]
            out.extend(d_start)
        return tuple(out)

    d_states = lax.fori_loop(0, chunks, one_chunk,
                             tuple(d_state_ref[h] for h in range(heads)))
    for h, d_state in enumerate(d_states):
        d_state_ref[h] = d_state


class _Call(NamedTuple):
    """The shapes and specs both kernels share."""
    plan: KDAPlan
    b: int
    h: int
    length: int
    padded: int
    n: int
    dk: int
    dv: int
    grid: tuple
    wide: object            # BlockSpec maker for [B, H, L, d] arrays
    beta: object
    states: object
    params: object


def _prepare(q, v, chunk, dtype, reverse) -> _Call:
    b, h, length, dk = q.shape
    dv = v.shape[-1]
    if chunk & (chunk - 1):
        raise ValueError(f"chunk must be a power of two, got {chunk}")
    plan = kda_plan(length, h, dk, dv, chunk, dtype)
    span = plan.chunks * chunk
    padded = -(-length // span) * span
    steps = padded // span
    along = (lambda i: steps - 1 - i) if reverse else (lambda i: i)
    wide = lambda d: pl.BlockSpec(
        (1, plan.heads, span, d), lambda b_, g_, i: (b_, g_, along(i), 0))
    beta = pl.BlockSpec((1, plan.heads, plan.chunks, 1, chunk),
                        lambda b_, g_, i: (b_, g_, along(i), 0, 0))
    states = pl.BlockSpec((1, plan.heads, plan.chunks, dv, dk),
                          lambda b_, g_, i: (b_, g_, along(i), 0, 0))
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=plan.vmem_bytes + 16 * 2 ** 20)
    return _Call(plan, b, h, length, padded, padded // chunk, dk, dv,
                 (b, h // plan.heads, steps), wide, beta, states, params)


def _pad(x, call: _Call):
    """The tail is tokens that neither write (beta 0) nor decay (g 0)."""
    extra = call.padded - call.length
    if not extra:
        return x
    return jnp.pad(x, ((0, 0), (0, 0), (0, extra)) + ((0, 0),) * (x.ndim - 3))


def _beta_chunks(beta, call: _Call, chunk):
    """[B, H, L] -> [B, H, N, 1, C]: a chunk's beta is a row a step can
    pick by the chunk's number."""
    return _pad(beta, call).reshape(call.b, call.h, call.n, 1, chunk)


def kda_scan_fwd(q, k, v, g, beta, chunk: int = 64, sub: int = 16,
                 dtype=jnp.float32, save_states: bool = False,
                 interpret: bool = False):
    """The forward kernel: `kda_chunked`'s arguments and result, and with
    ``save_states`` also each chunk's starting state [B, H, N, dv, dk]
    float32 (transposed), which :func:`kda_scan_bwd` reads."""
    call = _prepare(q, v, chunk, dtype, reverse=False)
    plan = call.plan
    kernel = functools.partial(
        _fwd_kernel, heads=plan.heads, chunks=plan.chunks, chunk=chunk,
        sub=sub, dtype=dtype, save=save_states)
    out_shape = [jax.ShapeDtypeStruct((call.b, call.h, call.padded, call.dv),
                                      _F32)]
    out_specs = [call.wide(call.dv)]
    if save_states:
        out_shape.append(jax.ShapeDtypeStruct(
            (call.b, call.h, call.n, call.dv, call.dk), _F32))
        out_specs.append(call.states)
    out = pl.pallas_call(
        kernel, grid=call.grid,
        in_specs=[call.wide(call.dk), call.wide(call.dk), call.wide(call.dv),
                  call.wide(call.dk), call.beta],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((plan.heads, call.dv, call.dk), _F32)],
        compiler_params=call.params, interpret=interpret,
        name="kda_scan_fwd",
    )(_pad(q, call), _pad(k, call), _pad(v, call), _pad(g, call),
      _beta_chunks(beta, call, chunk))
    o = out[0][:, :, :call.length]
    return (o, out[1]) if save_states else o


def kda_scan_bwd(q, k, v, g, beta, states, do, chunk: int = 64,
                 sub: int = 16, dtype=jnp.float32,
                 interpret: bool = False):
    """The backward kernel: (dq, dk, dv, dg, dbeta) in the inputs' shapes
    and dtypes, from the forward's saved states and ``do``."""
    call = _prepare(q, v, chunk, dtype, reverse=True)
    plan = call.plan
    kernel = functools.partial(
        _bwd_kernel, heads=plan.heads, chunks=plan.chunks, chunk=chunk,
        sub=sub, dtype=dtype)
    wide = lambda d, dt: jax.ShapeDtypeStruct(
        (call.b, call.h, call.padded, d), dt)
    dq, dk, dv, dg, dbeta = pl.pallas_call(
        kernel, grid=call.grid,
        in_specs=[call.wide(call.dk), call.wide(call.dk), call.wide(call.dv),
                  call.wide(call.dk), call.beta, call.states,
                  call.wide(call.dv)],
        out_specs=[call.wide(call.dk), call.wide(call.dk),
                   call.wide(call.dv), call.wide(call.dk), call.beta],
        out_shape=[wide(call.dk, _F32), wide(call.dk, _F32),
                   wide(call.dv, v.dtype), wide(call.dk, _F32),
                   jax.ShapeDtypeStruct(
                       (call.b, call.h, call.n, 1, chunk), _F32)],
        scratch_shapes=[pltpu.VMEM((plan.heads, call.dv, call.dk), _F32)],
        compiler_params=call.params, interpret=interpret,
        name="kda_scan_bwd",
    )(_pad(q, call), _pad(k, call), _pad(v, call), _pad(g, call),
      _beta_chunks(beta, call, chunk), states, _pad(do.astype(_F32), call))
    cut = lambda x, like: x[:, :, :call.length].astype(like.dtype)
    dbeta = dbeta.reshape(call.b, call.h, call.padded)
    return (cut(dq, q), cut(dk, k), cut(dv, v), cut(dg, g), cut(dbeta, beta))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def kda_scan(q, k, v, g, beta, chunk: int = 64, sub: int = 16,
             dtype=jnp.float32, interpret: bool = False):
    """`kda_chunked` as the kernel pair, differentiable: q, k, g
    [B, H, L, dk], v [B, H, L, dv], beta [B, H, L] -> o [B, H, L, dv]
    float32.  Scope ``kda/scan`` holds the forward and the backward."""
    with profile_scope(_SCOPE, "kernel"):
        return kda_scan_fwd(q, k, v, g, beta, chunk, sub, dtype,
                            interpret=interpret)


def _scan_fwd(q, k, v, g, beta, chunk, sub, dtype, interpret):
    with profile_scope(_SCOPE, "kernel"):
        o, states = kda_scan_fwd(q, k, v, g, beta, chunk, sub, dtype,
                                 save_states=True, interpret=interpret)
    return o, (q, k, v, g, beta, states)


def _scan_bwd(chunk, sub, dtype, interpret, res, do):
    with profile_scope(_SCOPE, "kernel"):
        return kda_scan_bwd(*res, do, chunk, sub, dtype,
                            interpret=interpret)


kda_scan.defvjp(_scan_fwd, _scan_bwd)
