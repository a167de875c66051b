"""Fused optimizer apply: one Pallas pass per flat gradient bucket.

The unfused hot path runs the optax chain once per pytree leaf —
``tx.update`` traces a momentum multiply-add (or the Adam moment pair)
for every parameter tensor, then ``optax.apply_updates`` adds the
update back, so a ResNet's weight update lowers to hundreds of small
elementwise loops with one HBM round trip each.  The PR 1 bucket engine
(compression/bucketing.py) already lays the gradient out as a few
contiguous fp32 buckets for the wire; this module applies SGD-momentum
or Adam directly on that layout, one VMEM-resident Pallas pass per
bucket: read param/grad/moment tiles once, write the new param and
moment tiles once (``input_output_aliases`` keeps the update in place).
"Automatic Cross-Replica Sharding of Weight Update in Data-Parallel
Training" (PAPERS.md) motivates the same fusion for the sharded update:
the kernels are shape-agnostic over flat fp32 vectors, so the ZeRO path
(train/zero.py) feeds them its 1/W bucket shards unchanged.

Contract (same as ``bsc_pallas``):

- hyperparameters are STATIC (baked into the kernel at trace time), so
  the optimizer must be built by :func:`fused_optimizer`, which wraps
  the equivalent optax transformation and carries a
  :class:`FusedOptimSpec` the train step can read — a plain optax
  closure hides its learning rate and is rejected loudly;
- the jnp reference paths (:func:`sgd_momentum_ref`, :func:`adam_ref`)
  mirror the kernel's operation order exactly and are the parity
  oracle in interpret mode (tests/test_optim_pallas.py): the moment
  buffers are bitwise-identical, and the updated params agree to one
  rounding of the final update subtract (XLA may contract the trailing
  multiply-subtract into an FMA in one of the two separately compiled
  programs but not the other; asserted at rtol=1e-6/atol=1e-8, tighter
  than the ``bsc_pallas`` parity suite's atol=1e-6);
- state layout is the unmodified optax state over the bucket (or
  bucket-shard) list — ``tx.init(buckets)`` — so checkpoints and the
  ZeRO reshard helpers keep working, and the fused and unfused paths
  are freely interchangeable between runs;
- Adam's bias corrections ``1 - beta**t`` depend on the traced step
  count, so they enter the kernel as (1, 1) SMEM scalars; everything
  elementwise stays inside the kernel (tests/test_optim_pallas.py
  pins that the lowered fused module contains NO
  ``stablehlo.multiply`` — every flop of the update lives behind the
  ``tpu_custom_call``).

The optional ``cast_dtype`` emits an extra low-precision copy of the
updated master weights in the same pass (the "master-weight cast" for
workloads that keep a separate bf16 working copy); the in-repo bf16
mode does not need it — flax casts per-op from the fp32 masters — but
the kernel output is there and parity-tested.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

_LANES = 128
_BLK_ROWS = 256         # [256, 128] fp32 tiles: 128 KiB per operand block


class FusedOptimSpec(NamedTuple):
    """Static hyperparameters of a fused-apply optimizer."""

    kind: str               # "sgd" (momentum SGD) | "adam"
    learning_rate: float
    momentum: float = 0.0   # sgd only
    b1: float = 0.9         # adam only
    b2: float = 0.999
    eps: float = 1e-8


@dataclasses.dataclass(frozen=True)
class FusedOptimizer:
    """An optax-compatible (init/update) optimizer carrying the static
    spec the fused kernels need.  ``init``/``update`` delegate to the
    equivalent optax chain, so with ``GEOMX_FUSED_OPTIM`` off this is
    exactly the per-leaf optimizer it replaces."""

    spec: FusedOptimSpec
    init: Callable
    update: Callable


def fused_optimizer(kind: str, *, learning_rate: float,
                    momentum: float = 0.9, b1: float = 0.9,
                    b2: float = 0.999, eps: float = 1e-8) -> FusedOptimizer:
    """Build a :class:`FusedOptimizer` ("sgd" with momentum, or "adam").

    The wrapped optax transformation defines the semantics; the fused
    kernels replace its per-leaf trace only when the step is built with
    ``GEOMX_FUSED_OPTIM=1`` / ``GeoConfig(fused_optim=True)``."""
    import optax

    kind = str(kind).lower()
    if kind == "sgd":
        tx = optax.sgd(learning_rate, momentum=momentum)
        spec = FusedOptimSpec("sgd", float(learning_rate),
                              momentum=float(momentum))
    elif kind == "adam":
        tx = optax.adam(learning_rate, b1=b1, b2=b2, eps=eps)
        spec = FusedOptimSpec("adam", float(learning_rate), b1=float(b1),
                              b2=float(b2), eps=float(eps))
    else:
        raise ValueError(f"fused_optimizer: unknown kind {kind!r} "
                         "(supported: 'sgd', 'adam')")
    return FusedOptimizer(spec=spec, init=tx.init, update=tx.update)


def fused_spec_of(tx: Any) -> Optional[FusedOptimSpec]:
    """The static spec if ``tx`` was built by :func:`fused_optimizer`."""
    spec = getattr(tx, "spec", None)
    return spec if isinstance(spec, FusedOptimSpec) else None


def fused_optim_enabled(config=None) -> bool:
    """Static build-time gate, same contract as
    ``telemetry.probes.telemetry_enabled``: the config field wins, the
    environment covers config-less call sites."""
    if config is not None and getattr(config, "fused_optim", False):
        return True
    from geomx_tpu.config import _env_bool
    return _env_bool(["GEOMX_FUSED_OPTIM"], False)


# ---------------------------------------------------------------------------
# jnp references: the bitwise parity oracles (identical operation order)
# ---------------------------------------------------------------------------

def sgd_momentum_ref(p, g, m, *, lr, momentum):
    """m' = momentum*m + g;  p' = p - lr*m'  (optax.sgd trace+scale)."""
    m2 = momentum * m + g
    return p - lr * m2, m2


def adam_ref(p, g, m, v, bc1, bc2, *, lr, b1, b2, eps):
    """One Adam step with the bias corrections ``bc = 1 - beta**t``
    passed in (computed from the traced count by :func:`fused_apply`,
    exactly as the kernel receives them through SMEM)."""
    m2 = b1 * m + (1.0 - b1) * g
    v2 = b2 * v + (1.0 - b2) * (g * g)
    mh = m2 / bc1
    vh = v2 / bc2
    return p - lr * (mh / (jnp.sqrt(vh) + eps)), m2, v2


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _pad2d(a: jax.Array, blk: int) -> Tuple[jax.Array, int]:
    """Flat fp32 [n] -> [rows, 128] with rows a multiple of ``blk``
    (zero-filled tail; the caller slices back to n).  Explicit padding
    keeps the grid an exact tiling — no reliance on edge-block masking
    semantics, and zero tails stay zero through both optimizers."""
    n = a.shape[0]
    rows = -(-max(n, 1) // _LANES)
    rows = -(-rows // blk) * blk
    npad = rows * _LANES
    if npad != n:
        a = jnp.pad(a, (0, npad - n))
    return a.reshape(rows, _LANES), n


def _sgd_kernel(p_ref, g_ref, m_ref, op_ref, om_ref, *extra,
                lr, momentum, cast_dtype):
    m = momentum * m_ref[...] + g_ref[...]
    p = p_ref[...] - lr * m
    om_ref[...] = m
    op_ref[...] = p
    if cast_dtype is not None:
        extra[0][...] = p.astype(cast_dtype)


def _adam_kernel(bc1_ref, bc2_ref, p_ref, g_ref, m_ref, v_ref,
                 op_ref, om_ref, ov_ref, *extra, lr, b1, b2, eps,
                 cast_dtype):
    g = g_ref[...]
    m = b1 * m_ref[...] + (1.0 - b1) * g
    v = b2 * v_ref[...] + (1.0 - b2) * (g * g)
    mh = m / bc1_ref[0, 0]
    vh = v / bc2_ref[0, 0]
    p = p_ref[...] - lr * (mh / (jnp.sqrt(vh) + eps))
    om_ref[...] = m
    ov_ref[...] = v
    op_ref[...] = p
    if cast_dtype is not None:
        extra[0][...] = p.astype(cast_dtype)


@functools.partial(jax.jit, static_argnames=("lr", "momentum", "cast_dtype",
                                             "interpret"))
def fused_sgd_momentum(p: jax.Array, g: jax.Array, m: jax.Array, *,
                       lr: float, momentum: float,
                       cast_dtype=None, interpret: bool = False):
    """One fused SGD-momentum step over a flat fp32 vector.

    Returns ``(new_p, new_m)`` (plus the ``cast_dtype`` copy of the new
    params when requested).  Parity with :func:`sgd_momentum_ref` in
    interpret mode: moments bitwise, params to one final rounding."""
    import jax.experimental.pallas as pl

    blk = _BLK_ROWS if p.shape[0] > _BLK_ROWS * _LANES else 8
    p2, n = _pad2d(p.astype(jnp.float32), blk)
    g2, _ = _pad2d(g.astype(jnp.float32), blk)
    m2, _ = _pad2d(m.astype(jnp.float32), blk)
    rows = p2.shape[0]
    spec = pl.BlockSpec((blk, _LANES), lambda i: (i, 0))
    out_shape = [jax.ShapeDtypeStruct((rows, _LANES), jnp.float32)] * 2
    out_specs = [spec, spec]
    if cast_dtype is not None:
        out_shape.append(jax.ShapeDtypeStruct((rows, _LANES),
                                              jnp.dtype(cast_dtype)))
        out_specs.append(spec)
    outs = pl.pallas_call(
        functools.partial(_sgd_kernel, lr=lr, momentum=momentum,
                          cast_dtype=(None if cast_dtype is None
                                      else jnp.dtype(cast_dtype))),
        grid=(rows // blk,),
        in_specs=[spec, spec, spec],
        out_specs=out_specs,
        out_shape=out_shape,
        input_output_aliases={0: 0, 2: 1},
        interpret=interpret,
    )(p2, g2, m2)
    return tuple(o.reshape(-1)[:n] for o in outs)


@functools.partial(jax.jit, static_argnames=("lr", "b1", "b2", "eps",
                                             "cast_dtype", "interpret"))
def fused_adam(p: jax.Array, g: jax.Array, m: jax.Array, v: jax.Array,
               bc1: jax.Array, bc2: jax.Array, *, lr: float, b1: float,
               b2: float, eps: float, cast_dtype=None,
               interpret: bool = False):
    """One fused Adam step over a flat fp32 vector; ``bc1``/``bc2`` are
    the scalar bias corrections ``1 - beta**t`` (traced — they ride
    SMEM, so the step count never recompiles the kernel).  Returns
    ``(new_p, new_m, new_v)`` (+ the cast copy).  Parity with
    :func:`adam_ref` in interpret mode: moments bitwise, params to one
    final rounding."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    blk = _BLK_ROWS if p.shape[0] > _BLK_ROWS * _LANES else 8
    p2, n = _pad2d(p.astype(jnp.float32), blk)
    g2, _ = _pad2d(g.astype(jnp.float32), blk)
    m2, _ = _pad2d(m.astype(jnp.float32), blk)
    v2, _ = _pad2d(v.astype(jnp.float32), blk)
    rows = p2.shape[0]
    spec = pl.BlockSpec((blk, _LANES), lambda i: (i, 0))
    sspec = pl.BlockSpec(memory_space=pltpu.SMEM)
    out_shape = [jax.ShapeDtypeStruct((rows, _LANES), jnp.float32)] * 3
    out_specs = [spec, spec, spec]
    if cast_dtype is not None:
        out_shape.append(jax.ShapeDtypeStruct((rows, _LANES),
                                              jnp.dtype(cast_dtype)))
        out_specs.append(spec)
    outs = pl.pallas_call(
        functools.partial(_adam_kernel, lr=lr, b1=b1, b2=b2, eps=eps,
                          cast_dtype=(None if cast_dtype is None
                                      else jnp.dtype(cast_dtype))),
        grid=(rows // blk,),
        in_specs=[sspec, sspec, spec, spec, spec, spec],
        out_specs=out_specs,
        out_shape=out_shape,
        input_output_aliases={2: 0, 4: 1, 5: 2},
        interpret=interpret,
    )(jnp.asarray(bc1, jnp.float32).reshape(1, 1),
      jnp.asarray(bc2, jnp.float32).reshape(1, 1), p2, g2, m2, v2)
    return tuple(o.reshape(-1)[:n] for o in outs)


# ---------------------------------------------------------------------------
# the bucket-list apply (what the train step and the ZeRO plan call)
# ---------------------------------------------------------------------------

def fused_apply(spec: FusedOptimSpec, params: Sequence[jax.Array],
                grads: Sequence[jax.Array], opt_state: Any, *,
                interpret: bool = False,
                use_ref: bool = False) -> Tuple[List[jax.Array], Any]:
    """Apply one optimizer step over flat fp32 buckets (or 1/W bucket
    shards) in place of ``tx.update`` + ``optax.apply_updates``.

    ``opt_state`` is the unmodified optax state from ``tx.init`` over
    the same bucket list — its structure round-trips exactly (TraceState
    / ScaleByAdamState + the chain tail), so checkpoints and reshard
    helpers never see a new layout.  ``use_ref=True`` runs the jnp
    reference math instead of the kernels (the parity/fallback path —
    same state contract, bitwise-equal in interpret mode)."""
    import optax

    inner, rest = opt_state[0], tuple(opt_state[1:])
    params = list(params)
    grads = list(grads)
    if spec.kind == "sgd":
        tleaves, tdef = jax.tree.flatten(inner.trace)
        if len(tleaves) != len(params):
            raise ValueError(
                f"fused_apply: optimizer trace has {len(tleaves)} buckets "
                f"but the layout needs {len(params)} — opt_state was "
                "initialized from a different bucket list")
        new_p, new_m = [], []
        for p, g, m in zip(params, grads, tleaves):
            if use_ref:
                np_, nm = sgd_momentum_ref(p, g, m, lr=spec.learning_rate,
                                           momentum=spec.momentum)
            else:
                np_, nm = fused_sgd_momentum(p, g, m,
                                             lr=spec.learning_rate,
                                             momentum=spec.momentum,
                                             interpret=interpret)
            new_p.append(np_)
            new_m.append(nm)
        new_inner = optax.TraceState(trace=tdef.unflatten(new_m))
        return new_p, (new_inner,) + rest
    if spec.kind == "adam":
        mleaves, mdef = jax.tree.flatten(inner.mu)
        vleaves, _ = jax.tree.flatten(inner.nu)
        if len(mleaves) != len(params):
            raise ValueError(
                f"fused_apply: optimizer moments have {len(mleaves)} "
                f"buckets but the layout needs {len(params)} — opt_state "
                "was initialized from a different bucket list")
        count = optax.safe_int32_increment(inner.count)
        t = count.astype(jnp.float32)
        bc1 = 1.0 - spec.b1 ** t
        bc2 = 1.0 - spec.b2 ** t
        new_p, new_m, new_v = [], [], []
        for p, g, m, v in zip(params, grads, mleaves, vleaves):
            if use_ref:
                np_, nm, nv = adam_ref(p, g, m, v, bc1, bc2,
                                       lr=spec.learning_rate, b1=spec.b1,
                                       b2=spec.b2, eps=spec.eps)
            else:
                np_, nm, nv = fused_adam(p, g, m, v, bc1, bc2,
                                         lr=spec.learning_rate,
                                         b1=spec.b1, b2=spec.b2,
                                         eps=spec.eps, interpret=interpret)
            new_p.append(np_)
            new_m.append(nm)
            new_v.append(nv)
        new_inner = optax.ScaleByAdamState(count=count,
                                           mu=mdef.unflatten(new_m),
                                           nu=mdef.unflatten(new_v))
        return new_p, (new_inner,) + rest
    raise ValueError(f"fused_apply: unknown spec kind {spec.kind!r}")


def unfused_apply(tx, params: Sequence[jax.Array],
                  grads: Sequence[jax.Array],
                  opt_state: Any) -> Tuple[List[jax.Array], Any]:
    """The per-leaf optax chain over the same bucket list — the
    structural baseline the DCE gate lowers next to ``fused_apply``."""
    import optax

    params = list(params)
    updates, opt_state = tx.update(list(grads), opt_state, params)
    return optax.apply_updates(params, updates), opt_state
