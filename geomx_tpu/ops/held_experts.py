"""The routed experts one chip holds, of an expert layer that routes over
all of them (expert parallelism's per-chip part, without the exchange).

Every token chooses ``k`` of ``num_experts`` experts; this chip holds
``E`` of them, ids ``[offset, offset + E)``.  The assignments that fall
on held experts are sorted by expert and walked in pools of consecutive
assignments: gather the pool's tokens once, run the experts as grouped
products over the experts' runs inside the pool (JAX's megablox kernels:
a tile of ``rows`` assignments at a time, only the tiles that hold an
assignment, each with its expert's weights), scatter-add the weighted
result once.  An expert is a SwiGLU, ``(silu(x W_gate) * x W_up)
W_down``, or, where no gate is given, the un-gated squared ReLU
``relu(x W_up)^2 W_down`` (a LatentMoE's experts, which live in a latent
width): the same plan, pools, row moves and products, one product fewer
a pool each way.  The first pool has ``pool`` places (``2 * E * rows`` where
none is given: twice what even routing sends here when ``rows`` is an
expert's share; a caller whose experts see more gives twice its own even
load, a multiple of ``rows``) and is always walked, so up to twice even
load the layer's program does not change with what the router does.  The
pool's rows come in by XLA's row gather, which costs what its bytes cost
inside a program (6 ns a place on a v5e, PERF.md; a place past the runs
reads a real row too, so the gather has nothing to fill), and go back through
``ops.dispatch.row_scatter_add`` (both under scope ``moe/dispatch``): on
a TPU, at a width of whole tiles (a multiple of 1,024: the door says
why), the kernel of ``ops/moe_rows_pallas.py``, two copies a row that
arrived and none for a tile of places that holds no assignment, so the
move costs the rows, not the places; elsewhere XLA's own scatter-add,
which on a v5e pays by the index whether the row exists or not (93 ns a
place in the step: why the first pool was made large and walked whatever
arrives).
The plan (scope ``moe/plan``) is the one thing that pays by the routed
assignment, held or not: ONE stable `lax.sort` of all ``T k`` by held
expert carries each assignment's index and weight with the key, and the
backward pass brings the weights' gradient back to ``[T, k]`` by a sort by
that index (a permutation, so the sort is its inverse).  A 1-D gather or
scatter of ``T k`` scalars costs a v5e 5-7 ns an index, many times a
sort's extra operand (PERF.md, PR 39): nothing here gathers or scatters
over ``T k``.
What arrives beyond the first pool is walked in pools of
``2 * rows`` by a loop of as many trips as it needs: one expert may take
every token (``T`` rows, the worst case) and nothing is dropped, because
no capacity exists to overflow.  The walk counts the rows it processed;
`dropped` is what arrived less that.

The grouped products skip a pool's tiles that hold no assignment and the
row moves pay by the row, so a layer's time follows the router's load
(0.16 ms a tile of 512 forward and backward at K = 1,024, N = 2,688 on a
v5e: PERF.md, PR 38).

Nothing is masked or scaled at ``[places, width]``.  The grouped products
write and read the runs' rows only (a boundary tile's other rows are kept
out by a select in the kernel, never by a product), and the scatter-add
drops every place past the runs, so those rows of a pool's arrays hold
whatever the kernels left there, NaN included, and no pass zeroes them.
A routing weight is linear through the second product, so it goes into
the hidden layer inside the fusion that makes it; the one sum over a
row that leaves the pool, the weights' gradient, is masked as a
``[places]`` vector, by a select.

A loop with a data-dependent trip count has no reverse-mode derivative in
JAX, so the backward pass is written out (`custom_vjp`): the same walk,
each pool's hidden layer recomputed, weight gradients accumulated in the
kernels' output.

The grouped-product kernels run natively on a TPU and in Pallas interpret
mode elsewhere; the row moves take their jnp forms off a TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

from geomx_tpu.ops.dispatch import kernel_mode, row_scatter_add
from geomx_tpu.utils.profiler import profile_scope

# the pools' row moves, forward and backward, whichever implementation
# `ops/dispatch.py` picks (telemetry/layers.SCOPES)
_DISPATCH = "moe/dispatch"
# the plan: the sort of the routed assignments that carries token and
# weight, the counts, and the sort that brings the weights' gradient back
_PLAN = "moe/plan"

# the kernels' tiles over the contracted and the output dimension, at most
GMM_TILES = (1152, 768)
TGMM_TILES = (768, 512)


def _tile(n: int, cap: int) -> int:
    """The largest multiple of 128 up to `cap` that divides `n`, or all of
    `n` where it is no larger than `cap` (or has no such divisor)."""
    if n <= cap:
        return n
    fits = [t for t in range(128, cap + 1, 128) if n % t == 0]
    return fits[-1] if fits else n


def _gmm(lhs, rhs, sizes, rows, interpret, transpose_rhs=False):
    """lhs [m, k] x rhs [E, k, n] (or [E, n, k], transposed) by runs of
    `sizes` rows -> [m, n] float32; rows past the runs are neither read
    into a result nor written: they hold what the buffer held."""
    k = lhs.shape[1]
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    return gmm(lhs, rhs, sizes, jnp.float32,
               (rows, _tile(k, GMM_TILES[0]), _tile(n, GMM_TILES[1])),
               transpose_rhs=transpose_rhs, interpret=interpret)


def _tgmm(lhs, rhs, sizes, rows, interpret, into):
    """`into` [E, k, n] float32 plus, for each run of `sizes` rows,
    lhs[run]^T [k, rows] x rhs[run] [rows, n]."""
    return tgmm(lhs.T, rhs, sizes, jnp.float32,
                (rows, _tile(lhs.shape[1], TGMM_TILES[0]),
                 _tile(rhs.shape[1], TGMM_TILES[1])),
                existing_out=into, interpret=interpret)


def _pools(num_held: int, rows: int, assignments: int, pool=None):
    """(places of the first pool, of each later one, of all that the
    sorted assignments are padded to)."""
    first, later = pool or 2 * num_held * rows, 2 * rows
    if first % rows:
        raise ValueError(f"a first pool of {first} places is not whole "
                         f"tiles of {rows}")
    beyond = -(-max(assignments - first, 0) // later) * later
    return first, later, first + beyond


def _plan(idx, weights, num_held: int, offset: int, rows: int, pool=None):
    """The held assignments in order of their expert, padded to whole
    pools.  idx [T, k] global expert ids, weights [T, k].  One stable sort
    by held expert carries each assignment's index and weight along."""
    t, k = idx.shape
    with profile_scope(_PLAN, "compute"):
        local = idx.reshape(-1) - offset
        held = (local >= 0) & (local < num_held)
        key = jnp.where(held, local, num_held).astype(jnp.int32)
        _, order, weight = lax.sort(
            (key, lax.iota(jnp.int32, t * k),
             weights.reshape(-1).astype(jnp.float32)),
            num_keys=1, is_stable=True)
        counts = jnp.sum(key[:, None] == jnp.arange(num_held)[None, :],
                         axis=0, dtype=jnp.int32)                  # [E]
        pad = (0, _pools(num_held, rows, t * k, pool)[2] - t * k)
        return {"token": jnp.pad(order // k, pad),
                "weight": jnp.pad(weight, pad),
                "order": order, "counts": counts,
                "ends": jnp.cumsum(counts), "tokens": t}


def _walk(plan, num_held: int, rows: int, pool, body, carry):
    """`body(lo, places, carry)` over the first pool, always, and over as
    many later ones as the held assignments reach."""
    first, later, _ = _pools(num_held, rows, plan["order"].size, pool)
    carry = body(0, first, carry)
    trips = -(-jnp.maximum(plan["ends"][-1] - first, 0) // later)
    return lax.fori_loop(
        0, trips, lambda c, carry: body(first + c * later, later, carry),
        carry)


def places_walked(assignments, num_held: int, rows: int, pool=None):
    """Places the walk covers when `assignments` (a traced count) fall on
    the held experts: the first pool and the later ones they reach."""
    first, later, _ = _pools(num_held, rows, 0, pool)
    return first + -(-jnp.maximum(assignments - first, 0) // later) * later


def _pool(plan, lo, pool: int):
    """(the tokens to gather, the tokens to scatter to, weights, valid,
    rows of each expert's run) of the `pool` sorted assignments from `lo`
    on.  A place past the last held assignment gathers a real row (the
    token of an assignment held elsewhere, or of the padding: nothing
    reads what it gives) and scatters outside the token range (dropped)."""
    i = jnp.arange(pool, dtype=jnp.int32)
    valid = lo + i < plan["ends"][-1]
    source = lax.dynamic_slice(plan["token"], (lo,), (pool,))
    target = jnp.where(valid, source, plan["tokens"] + i)
    weight = lax.dynamic_slice(plan["weight"], (lo,), (pool,))
    ends = jnp.clip(plan["ends"], lo, lo + pool)
    starts = jnp.clip(plan["ends"] - plan["counts"], lo, lo + pool)
    return source, target, weight, valid, ends - starts


def _gather(a, source):
    """The pool's rows of `a` [T, d]; every id of `source` is a token's."""
    with profile_scope(_DISPATCH, "kernel"):
        return a.at[source].get(mode="promise_in_bounds")


def _hidden(xs, into, sizes, rows, interpret, gated: bool):
    """(the first product ``xs`` x ``into``, the hidden layer it gives) of
    a pool, float32, in the runs' rows.  Gated: the product is [a, u] and
    the hidden layer silu(a) * u; else relu(a)^2."""
    pre = _gmm(xs, into, sizes, rows, interpret)
    if not gated:
        return pre, jnp.square(jax.nn.relu(pre))
    a, u = jnp.split(pre, 2, axis=-1)
    return pre, jax.nn.silu(a) * u


def _hidden_bwd(pre, dh, gated: bool):
    """The first product's cotangent from the hidden layer's."""
    if not gated:
        return dh * 2.0 * jax.nn.relu(pre)
    a, u = jnp.split(pre, 2, axis=-1)
    sig = jax.nn.sigmoid(a)
    return jnp.concatenate([dh * u * sig * (1.0 + a * (1.0 - sig)),
                            dh * a * sig], axis=-1)


def _cast(x, gate, up, down):
    """(the first product's weights: [gate, up], or up alone where there
    is no gate; the second's), in x's dtype."""
    into = up if gate is None else jnp.concatenate([gate, up], axis=-1)
    return into.astype(x.dtype), down.astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def held_experts(x, idx, weights, gate, up, down, offset: int, rows: int,
                 interpret: bool | None = None, pool: int | None = None):
    """x [T, d] (the compute dtype), idx [T, k] int, weights [T, k] f32,
    gate / up [E, d, f], down [E, f, d] (the masters: they are cast to
    x's dtype once a call, and their gradients come back unrounded);
    ``gate`` None: un-gated squared-ReLU experts.
    `rows`: the assignments a kernel tile holds.  `interpret`: run the
    kernels in interpret mode; None: wherever the backend is no TPU.
    `pool`: the places of the first pool, whole tiles; None: 2 E rows.
    Returns (y [T, d] float32, counts [E] int32, dropped int32)."""
    return _forward(x, idx, weights, gate, up, down, offset, rows,
                    interpret, pool)[0]


def _forward(x, idx, weights, gate, up, down, offset, rows, interpret, pool):
    if interpret is None:
        interpret = kernel_mode() != "native"
    gated = gate is not None
    plan = _plan(idx, weights, up.shape[0], offset, rows, pool)
    into, down = _cast(x, gate, up, down)

    def body(lo, places, carry):
        y, done = carry
        source, target, weight, _, sizes = _pool(plan, lo, places)
        xs = _gather(x, source)
        h = _hidden(xs, into, sizes, rows, interpret, gated)[1]
        # (w h) W_down = w (h W_down): the weight at the hidden width
        out = _gmm((h * weight[:, None]).astype(x.dtype), down, sizes, rows,
                   interpret)
        with profile_scope(_DISPATCH, "kernel"):
            y = row_scatter_add(y, out, target, sizes)
        return y, done + jnp.sum(sizes)

    y, done = _walk(
        plan, up.shape[0], rows, pool, body,
        (jnp.zeros(x.shape, jnp.float32), jnp.zeros((), jnp.int32)))
    counts = plan["counts"]
    return (y, counts, jnp.sum(counts) - done), plan


def _fwd(x, idx, weights, gate, up, down, offset, rows, interpret, pool):
    out, plan = _forward(x, idx, weights, gate, up, down, offset, rows,
                         interpret, pool)
    return out, (x, idx, weights, gate, up, down, plan)


def _bwd(offset, rows, interpret, pool, res, cotangents):
    x, idx, weights, gate, up, down, plan = res
    if interpret is None:
        interpret = kernel_mode() != "native"
    dy = cotangents[0].astype(x.dtype)
    gated = gate is not None
    into, down_c = _cast(x, gate, up, down)

    def body(lo, places, carry):
        dx, dw, dinto, ddown = carry
        source, target, weight, valid, sizes = _pool(plan, lo, places)
        xs, dys = _gather(x, source), _gather(dy, source)
        pre, h = _hidden(xs, into, sizes, rows, interpret, gated)
        # <h W_down, dy> = <h, dy W_down^T>: one product gives the weight's
        # gradient and, scaled by the weight, the hidden layer's
        g = _gmm(dys, down_c, sizes, rows, interpret, True)
        # a select, not a product: a place past the runs may hold NaN
        dw = lax.dynamic_update_slice(
            dw, jnp.where(valid, jnp.sum(h * g, axis=-1), 0.0), (lo,))
        dpre = _hidden_bwd(pre, g * weight[:, None], gated).astype(x.dtype)
        # the operand the forward pass rounded, so no dys * weight is made
        ddown = _tgmm((h * weight[:, None]).astype(x.dtype), dys, sizes,
                      rows, interpret, ddown)
        dinto = _tgmm(xs, dpre, sizes, rows, interpret, dinto)
        dxs = _gmm(dpre, into, sizes, rows, interpret, True)
        with profile_scope(_DISPATCH, "kernel"):
            dx = row_scatter_add(dx, dxs, target, sizes)
        return dx, dw, dinto, ddown

    zeros = lambda a: jnp.zeros(a.shape, jnp.float32)
    dx, dw, dinto, ddown = _walk(
        plan, up.shape[0], rows, pool, body,
        (zeros(x), zeros(plan["weight"]), zeros(into), zeros(down)))
    dgate, dup = jnp.split(dinto, 2, axis=-1) if gated else (None, dinto)
    # back from sorted order to [T, k]: `order` is a permutation, so the
    # sort by it is its inverse (and no two keys tie: a stable sort would
    # carry an index beside them for nothing)
    with profile_scope(_PLAN, "compute"):
        dweights = lax.sort((plan["order"], dw[:idx.size]), num_keys=1,
                            is_stable=False)[1].reshape(weights.shape)
    return (dx.astype(x.dtype), None, dweights.astype(weights.dtype),
            dgate.astype(gate.dtype) if gated else None,
            dup.astype(up.dtype), ddown.astype(down.dtype))


held_experts.defvjp(_fwd, _bwd)
