"""In-graph step-health probes.

"Evaluation and Optimization of Gradient Compression for Distributed
Deep Learning" (PAPERS.md) makes the case that achieved compression and
error-feedback magnitude must be measured *in situ* — a bench-time
estimate says nothing about the ratio a production run is actually
getting, or about the step where a party's gradient went NaN.  These
probes compute that evidence as cheap scalars **inside the jitted
step**, riding the existing metrics output: no extra dispatch, no host
round trip beyond the device_get the training loop already does.

The master switch is ``GEOMX_TELEMETRY`` (or ``GeoConfig(telemetry=
True)``).  The gate is *static at trace time* and guards a single call
site in ``train/step.py``: with telemetry off, the traced step's jaxpr
is byte-identical to a build with this module excised (pinned by
``tests/test_telemetry.py``), so the default-off path costs exactly
nothing.

Probe catalog (all values replicated across the mesh, so they ride the
replicated metrics output):

- ``grad_norm_global``       L2 norm of the applied (post-sync) gradient
- ``grad_all_finite``        1.0 iff the applied gradient has no NaN/Inf
- ``grad_nonfinite_count``   number of non-finite applied-grad elements
- ``party_grad_nonfinite``   per-party 0/1 vector: party's RAW gradient
                             (pre-dc-aggregation) contains NaN/Inf —
                             the "which party is poisoning the mean"
                             signal the aggregated value hides
- ``dc_nonzero_fraction``    achieved density of the dc aggregate (the
                             in-situ sparsity a top-k compressor really
                             delivered, post-aggregation)
- ``ef_residual_norm``       party-mean L2 norm of the dc-tier error-
                             feedback state (sync.telemetry_scalars)
- ``bsc_emitted_fraction``   fraction of the fixed-k wire slots carrying
                             real (non-sentinel) pairs, recorded inline
                             by the BSC compressor per bucket
- ``pipeline_*``             staleness / in-flight accounting when the
                             pipelined engine is active
- ``dc_wire_bytes`` / ``dc_dense_bytes`` / ``dc_compression_ratio`` /
  ``worker_wire_bytes``      static per-step wire accounting
  (``sync.wire_accounting``), folded in as constants so the host plane
  reads one dict
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax


def canonicalize_jaxpr(text: str) -> str:
    """Strip run-dependent noise from a jaxpr's string form so two
    traces of the SAME program compare equal: the only non-deterministic
    tokens are function object addresses in custom_jvp thunk params
    (``<function ... at 0x...>``).  The jaxpr-identity verdict
    (tests/test_telemetry.py) compares on this."""
    import re
    return re.sub(r" at 0x[0-9a-fA-F]+>", " at 0xADDR>", text)


def telemetry_enabled(config: Optional[Any] = None) -> bool:
    """The master telemetry gate: ``config.telemetry`` or
    ``GEOMX_TELEMETRY``, parsed with the same numeric-boolean rules as
    every other GEOMX_* knob (``GeoConfig``'s ``_env_bool`` — so
    ``GEOMX_TELEMETRY=false`` raises loudly in BOTH readers instead of
    silently enabling here while the config rejects it).  Static —
    evaluated when the step program is *built*, so flipping it is a
    rebuild, never a silent recompile."""
    if config is not None and getattr(config, "telemetry", False):
        return True
    from geomx_tpu.config import _env_bool
    return _env_bool(["GEOMX_TELEMETRY"], False)


# ---------------------------------------------------------------------------
# inline recording: compressors deep inside the sync stack contribute
# probe scalars without threading a sink through every signature
# ---------------------------------------------------------------------------

_inline = threading.local()


@contextlib.contextmanager
def inline_collection():
    """Open a trace-time sink for :func:`record_inline`.  The traced
    step wraps its sync calls in this context only when telemetry is
    enabled, so the disabled path never even evaluates the probe
    expressions (``record_inline`` takes a thunk for exactly that
    reason)."""
    prev = getattr(_inline, "sink", None)
    sink: List[Tuple[str, jax.Array]] = []
    _inline.sink = sink
    try:
        yield sink
    finally:
        _inline.sink = prev


def inline_active() -> bool:
    return getattr(_inline, "sink", None) is not None


def record_inline(name: str, value_fn) -> None:
    """Record ``value_fn()`` (a traced scalar) under ``name`` into the
    active collection; no-op — without calling the thunk, so zero ops
    enter the jaxpr — when no collection is open."""
    sink = getattr(_inline, "sink", None)
    if sink is not None:
        sink.append((name, value_fn()))


# ---------------------------------------------------------------------------
# probe computation
# ---------------------------------------------------------------------------

def _float_leaves(tree) -> List[jax.Array]:
    return [leaf for leaf in jax.tree.leaves(tree)
            if hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype, jnp.floating)]


def _tree_sumsq(tree) -> jax.Array:
    leaves = _float_leaves(tree)
    if not leaves:
        return jnp.zeros((), jnp.float32)
    return sum(jnp.sum(jnp.square(leaf.astype(jnp.float32))) for leaf in leaves)


def tree_norm(tree) -> jax.Array:
    """L2 norm over every floating leaf of ``tree`` (0.0 when none)."""
    return jnp.sqrt(_tree_sumsq(tree))


def _nonfinite_count(tree) -> jax.Array:
    leaves = _float_leaves(tree)
    if not leaves:
        return jnp.zeros((), jnp.float32)
    return sum(jnp.sum((~jnp.isfinite(leaf)).astype(jnp.float32))
               for leaf in leaves)


def _replicate(x: jax.Array, sync: Any) -> jax.Array:
    """Party-local scalar -> mesh-replicated mean over LIVE parties
    (metrics out-spec is fully replicated).  Under a degraded membership
    mask the dead parties' devices still run the step (masked to zeros,
    residuals reset), so a plain dc pmean would dilute every probe by
    dead/total — the same survivor-weighted algebra step.py applies to
    loss/accuracy applies here."""
    from geomx_tpu.topology import DC_AXIS, WORKER_AXIS
    if getattr(sync, "workers_per_party", 1) > 1:
        x = lax.pmean(x, WORKER_AXIS)
    if getattr(sync, "num_parties", 1) > 1:
        w = sync.party_weight()
        if w is None:
            x = lax.pmean(x, DC_AXIS)
        else:
            x = lax.psum(x * w, DC_AXIS) / sync.num_live
    return x


def collect_step_probes(raw_grads: Any, synced_grads: Optional[Any],
                        sync: Any, sync_state: Any,
                        inline: Optional[List[Tuple[str, jax.Array]]],
                        params: Any) -> Dict[str, jax.Array]:
    """Assemble the probe dict inside the traced step.

    ``raw_grads``: this device's gradients before any cross-party
    aggregation (post sequence-parallel reduction); ``synced_grads``:
    the applied (dc-aggregated, replicated) gradient, or None on paths
    that fuse sync+update (MultiGPS); ``inline``: scalars recorded by
    compressors during the sync calls.  Every returned value is
    replicated across the mesh.
    """
    from geomx_tpu.topology import DC_AXIS, WORKER_AXIS
    nw = getattr(sync, "workers_per_party", 1)
    out: Dict[str, jax.Array] = {}

    # per-party NaN/Inf flag from the RAW gradients: aggregation (and a
    # mean over healthy parties) can mask one party's poison — the
    # per-party vector points at the culprit
    local_bad = _nonfinite_count(raw_grads)
    party_bad = lax.psum(local_bad, WORKER_AXIS) if nw > 1 else local_bad
    party_flag = (party_bad > 0).astype(jnp.float32)
    out["party_grad_nonfinite"] = lax.all_gather(party_flag, DC_AXIS)
    out["grad_nonfinite_parties"] = jnp.sum(out["party_grad_nonfinite"])

    if synced_grads is not None:
        # the applied gradient is replicated — no collective needed
        out["grad_norm_global"] = tree_norm(synced_grads)
        bad = _nonfinite_count(synced_grads)
        out["grad_nonfinite_count"] = bad
        out["grad_all_finite"] = (bad == 0).astype(jnp.float32)
        leaves = _float_leaves(synced_grads)
        total = sum(leaf.size for leaf in leaves) or 1
        nz = sum(jnp.sum((leaf != 0).astype(jnp.float32)) for leaf in leaves) \
            if leaves else jnp.zeros((), jnp.float32)
        out["dc_nonzero_fraction"] = nz / total

    # sync-algorithm scalars (EF residual norms, pipeline buffers):
    # party-local state, folded to the live-party mean
    for name, val in (sync.telemetry_scalars(sync_state) or {}).items():
        out[name] = _replicate(jnp.asarray(val, jnp.float32), sync)

    # inline recordings (e.g. BSC's per-bucket emitted fraction): mean
    # over recordings, then over the mesh
    if inline:
        grouped: Dict[str, List[jax.Array]] = {}
        for name, val in inline:
            grouped.setdefault(name, []).append(
                jnp.asarray(val, jnp.float32))
        for name, vals in grouped.items():
            mean = sum(vals) / len(vals)
            out[name] = _replicate(mean, sync)

    # static wire accounting as constants: the host plane reads probe
    # values and wire volume from the same dict
    for name, val in (sync.wire_accounting(params) or {}).items():
        out[name] = jnp.asarray(float(val), jnp.float32)
    return out
