"""Pipelined WAN sync: double-buffered staleness-1 dc-tier collectives.

The reference hides WAN latency with host-side machinery — P3's
priority-sliced pushes and DGT's off-critical-path channels (SURVEY.md
items 4-5) — and PR 1's bucketing cut the *number* of dc-tier
collectives, but every step still blocked on the DCN round trip before
the optimizer could run: the WAN latency sat squarely on the critical
path.  ``PipelinedSync`` takes it off entirely.

Step *t* launches the compressed dc-tier allreduce on step *t*'s
party-mean buckets, but the optimizer applies step *t-1*'s completed
aggregate, held in a double-buffer inside ``sync_state`` (the in-flight
buffer reuses the bucketed engine's flat fp32 layout,
compression/bucketing.py).  Because the collective's result is consumed
only by the *next* step, nothing in step *t*'s weight update waits on
the DCN — XLA's latency-hiding scheduler (and its collective pipeliner
on real multi-slice meshes) gets a full forward/backward of compute to
hide the WAN transfer behind.  This is the explicit double-buffering
Ok-Topk's sparse allreduce pipeline needs to reach overlap
(arXiv:2201.07598), applied at the tier EQuARX shows compressed
XLA-native collectives win at only when the scheduler can float them
(arXiv:2506.17615).

Semantics: staleness-1 data parallelism —

    w_{t+1} = w_t - lr * g_global(w_{t-1})

The first step is the pipeline's warmup bubble: it applies a zero
aggregate (the buffer starts empty) and only fills the pipeline; every
gradient is applied exactly once, one step late.  The optional
DCASGD-style compensation re-centers the stale aggregate at the weights
it is about to be applied to,

    g_comp = g + lambda * g * g * (w_t - w_{t-1})

reusing ``optim/dcasgd.py``'s correction term (reference
python/mxnet/optimizer/optimizer.py:872-925); ``w_{t-1}`` is tracked in
``sync_state`` (one extra params copy, allocated only when
``lambda > 0``).

Convergence note: a staleness-1 gradient roughly halves the stable
learning-rate headroom (the classic delayed-SGD bound) — at a stable lr
the pipelined trajectory matches the synchronous one to full accuracy
(tests/test_pipeline.py convergence parity), while an lr tuned to the
synchronous stability edge will oscillate.  That headroom is the price
paid for taking the DCN round trip off the critical path; the DCASGD
term buys some of it back.

The gradient's ICI tier (worker-axis mean) stays synchronous — intra-DC
latency is microseconds and the party-mean is the collective's input
anyway.  The model-state sync (BatchNorm stats) is double-buffered as a
whole: each step launches worker-pmean + dc-pmean of its fresh stats
into the buffer and applies the previous step's fully-aggregated stats,
so BOTH stat tiers are one step stale and NO dc-axis collective output
is consumed in-step (tests/test_pipeline.py verifies this
structurally in the DCE'd jaxpr).  ``lax.optimization_barrier`` separates the two tiers so
the flattened party-mean buckets are pinned as a unit before the DCN
launch and XLA cannot fuse the stale buffer's consumers into the
collective's dependency chain.

Composes with FSA and MixedSync by wrapping their dc-tier compressor.
HFA is rejected loudly — its global collective already fires every
K1*K2 steps off the step's critical path, and a stale milestone delta
would corrupt the milestone algebra.  MultiGPS is rejected in
``build_train_step`` (train/step.py): its ZeRO-1 update consumes the
dc-tier shard in-step by construction.

Checkpoint/restore: the in-flight buffers, the model-state buffer, and
the DCASGD previous-weights copy all live in ``sync_state``, so the
standard TrainState checkpoint round-trips the whole pipeline — a
resumed run continues the exact trajectory with no re-warmup.
"""

from __future__ import annotations

import copy
import os
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from geomx_tpu.compression.base import Compressor
from geomx_tpu.compression.bucketing import BucketedCompressor
from geomx_tpu.sync.base import SyncAlgorithm
from geomx_tpu.topology import DC_AXIS, WORKER_AXIS
from geomx_tpu.utils.profiler import get_profiler, profile_scope


def _resolve_depth(depth: Optional[int]) -> int:
    if depth is not None:
        return int(depth)
    # graftlint: disable=GXL006 — wrap-time knob
    raw = os.environ.get("GEOMX_PIPELINE_DEPTH")
    return int(float(raw)) if raw else 1


class PipelinedCompressor(Compressor):
    """Double-buffer any dc-tier compressor.

    ``allreduce`` launches the wrapped collective on this step's
    gradients, parks the result in its state, and returns the PREVIOUS
    step's completed aggregate — so the caller's downstream consumers
    (divide, optimizer) never depend on this step's collective.

    The in-flight buffer reuses the wrapped ``BucketedCompressor``'s
    flat fp32 bucket layout (one buffer per bucket, identical
    coordinates to the error-feedback state); with bucketing opted out
    it falls back to one leaf-shaped buffer per gradient leaf.
    """

    fuses_tree = True  # tree-level: never wrap in bucketing again

    def __init__(self, inner: Compressor):
        if isinstance(inner, PipelinedCompressor):
            raise ValueError("dc-tier compressor is already pipelined; "
                             "double-wrapping would add a second step of "
                             "staleness")
        self.inner = inner
        self.name = inner.name
        self._bucketed = isinstance(inner, BucketedCompressor)

    # -- state ---------------------------------------------------------------
    def init_state(self, grads: Any) -> Any:
        leaves = jax.tree.leaves(grads)
        if self._bucketed:
            bk = self.inner._bucketer(leaves)
            inflight: List[jax.Array] = [jnp.zeros((n,), jnp.float32)
                                         for n in bk.bucket_sizes]
        else:
            inflight = [jnp.zeros(jnp.shape(leaf), jnp.result_type(leaf))
                        for leaf in leaves]
        return {"inflight": inflight, "inner": self.inner.init_state(grads)}

    def init_leaf_state(self, leaf: jax.Array) -> Any:
        raise NotImplementedError(
            "PipelinedCompressor is tree-level (the in-flight buffer "
            "spans the whole gradient); per-leaf state is not supported")

    def init_shard_state(self, grads: Any, num_shards: int) -> Any:
        """ZeRO (train/zero.py): the in-flight double-buffer holds 1/W
        bucket *shards* — the aggregate parked between launch and apply
        shrinks with the worker axis exactly like the optimizer state."""
        if not self._bucketed:
            raise ValueError(
                "GEOMX_ZERO requires the bucketed dc-tier engine under "
                "the pipelined compressor (GEOMX_BUCKET_BYTES > 0)")
        leaves = jax.tree.leaves(grads)
        bk = self.inner._bucketer(leaves)
        inflight = [jnp.zeros((n // num_shards,), jnp.float32)
                    for n in bk.bucket_sizes]
        return {"inflight": inflight,
                "inner": self.inner.init_shard_state(grads, num_shards)}

    def zero_bucketer(self, leaves):
        return self.inner.zero_bucketer(leaves)

    def allreduce_shards(self, shards, state: Any, axis_name: str,
                         axis_size: int, bk) -> Tuple[List[jax.Array], Any]:
        """Double-buffered ZeRO dc tier: launch this step's per-shard
        compressed collectives, return the PREVIOUS step's completed
        shard aggregates — staleness-1 on shard-sized in-flight
        buffers."""
        prev = state["inflight"]
        # tier boundary, same contract as the replicated path: pin the
        # scattered party-mean shards as one unit before the DCN launch
        shards = list(lax.optimization_barrier(tuple(shards)))
        payload = sum(
            self.inner.inner.wire_bytes_leaf(
                jax.ShapeDtypeStruct((int(b.size),), jnp.float32))
            for b in shards)
        with profile_scope(f"{axis_name}_pipeline/launch",
                           category="comm",
                           args={"buckets": bk.num_buckets,
                                 "payload_bytes": payload}):
            launched, inner_state = self.inner.allreduce_shards(
                shards, state["inner"], axis_name, axis_size, bk)
        with profile_scope(f"{axis_name}_pipeline/apply", category="comm"):
            out = list(prev)
        return out, {"inflight": launched, "inner": inner_state}

    def peek_shards(self, state: Any) -> Tuple[List[jax.Array], Any]:
        """The completed in-flight shard aggregates plus state with the
        buffer zeroed — the ZeRO drain path."""
        prev = state["inflight"]
        zeroed = [jnp.zeros_like(b) for b in prev]
        return list(prev), dict(state, inflight=zeroed)

    # -- the double-buffered all-reduce --------------------------------------
    def allreduce(self, grads: Any, state: Any, axis_name: str,
                  axis_size: int) -> Tuple[Any, Any]:
        leaves, treedef = jax.tree.flatten(grads)
        if not leaves:
            return grads, state
        prev = state["inflight"]
        if self._bucketed:
            bk = self.inner._bucketer(leaves)
            buckets = bk.flatten(leaves)
            # tier boundary: pin the flattened ICI-tier party-mean as one
            # unit so the DCN launch below is a single scheduling island
            # XLA's latency-hiding scheduler can float — and nothing from
            # the stale-apply side fuses into its dependency chain
            buckets = list(lax.optimization_barrier(tuple(buckets)))
            with profile_scope(f"{axis_name}_pipeline/launch",
                               category="comm",
                               args={"buckets": bk.num_buckets,
                                     "payload_bytes": self.wire_bytes(grads)}):
                launched, inner_state = self.inner.allreduce_buckets(
                    buckets, state["inner"], axis_name, axis_size, bk)
            with profile_scope(f"{axis_name}_pipeline/apply",
                               category="comm"):
                out = treedef.unflatten(bk.unflatten(prev))
        else:
            pinned = treedef.unflatten(
                list(lax.optimization_barrier(tuple(leaves))))
            with profile_scope(f"{axis_name}_pipeline/launch",
                               category="comm",
                               args={"payload_bytes": self.wire_bytes(grads)}):
                launched_tree, inner_state = self.inner.allreduce(
                    pinned, state["inner"], axis_name, axis_size)
            launched = treedef.flatten_up_to(launched_tree)
            with profile_scope(f"{axis_name}_pipeline/apply",
                               category="comm"):
                out = treedef.unflatten(list(prev))
        # Chrome-trace counter: in-flight WAN bytes between launch/apply
        get_profiler().counter(f"{axis_name}_pipeline_inflight",
                               {"bytes": self.wire_bytes(grads)})
        return out, {"inflight": launched, "inner": inner_state}

    def allreduce_leaf(self, g: jax.Array, state: Any, axis_name: str,
                       axis_size: int) -> Tuple[jax.Array, Any]:
        raise NotImplementedError(
            "PipelinedCompressor is tree-level; the per-leaf path "
            "(MultiGPS) does not compose with pipelining")

    # -- draining ------------------------------------------------------------
    def peek(self, grads_like: Any, state: Any) -> Tuple[Any, Any]:
        """Return the completed in-flight aggregate as a gradient tree
        plus state with the buffer zeroed — the drain path (apply the
        last launched collective without feeding a new batch)."""
        leaves, treedef = jax.tree.flatten(grads_like)
        prev = state["inflight"]
        if self._bucketed:
            bk = self.inner._bucketer(leaves)
            out = treedef.unflatten(bk.unflatten(prev))
        else:
            out = treedef.unflatten(list(prev))
        zeroed = [jnp.zeros_like(b) for b in prev]
        return out, dict(state, inflight=zeroed)

    # -- accounting: same bytes per step as the wrapped path, one step late --
    def wire_bytes(self, grads: Any) -> int:
        return self.inner.wire_bytes(grads)

    def wire_bytes_leaf(self, leaf: jax.Array) -> int:
        return self.inner.wire_bytes_leaf(leaf)


class PipelinedSync(SyncAlgorithm):
    """Staleness-1 pipelined wrapper around FSA or MixedSync.

    Opt-in via ``GEOMX_PIPELINE_DEPTH=1`` (``get_sync_algorithm``) or by
    wrapping explicitly: ``PipelinedSync(FSA(...), dcasgd_lambda=0.04)``.
    """

    supports_degraded = True  # delegates the masked mean to FSA/MixedSync
    # the applied gradient is the previous step's completed dc aggregate
    # (plus a correction from replicated params) — replicated
    grads_replicated_after_sync = True

    def __init__(self, inner: SyncAlgorithm, depth: Optional[int] = None,
                 dcasgd_lambda: float = 0.0):
        from geomx_tpu.sync.fsa import FSA
        from geomx_tpu.sync.mixed import MixedSync
        if not isinstance(inner, (FSA, MixedSync)):
            # fail loudly (same contract as the MultiGPS check in
            # train/step.py): a user "running pipelined HFA" must not
            # silently get an unpipelined schedule or corrupt milestones
            raise ValueError(
                "GEOMX_PIPELINE_DEPTH composes with sync_mode=fsa or "
                f"mixed only, not {getattr(inner, 'name', type(inner).__name__)!r}: "
                "HFA's global tier already fires off the critical path "
                "every K1*K2 steps (a stale delta would corrupt the "
                "milestone algebra), and other algorithms have no "
                "per-step dc-tier collective to double-buffer")
        depth = _resolve_depth(depth)
        if depth != 1:
            raise ValueError(
                f"GEOMX_PIPELINE_DEPTH={depth} unsupported: only depth 1 "
                "(double buffering, staleness 1) is implemented — deeper "
                "pipelines need a ring buffer and staleness-k "
                "compensation, and hide no additional latency once the "
                "DCN round trip fits inside one step of compute")
        # shallow copy: installing the pipelined compressor must not
        # mutate the caller's algorithm — `PipelinedSync(fsa)` with `fsa`
        # also used as a synchronous baseline would silently make the
        # baseline staleness-1 too (compressor objects are stateless
        # config; their state lives in sync_state, so sharing them with
        # the original is safe)
        self.inner = copy.copy(inner)
        self.depth = depth
        self.dcasgd_lambda = float(dcasgd_lambda)
        self.name = f"pipelined_{inner.name}"
        if not isinstance(self.inner.dc_compressor, PipelinedCompressor):
            self.inner.dc_compressor = PipelinedCompressor(
                self.inner.dc_compressor)

    # -- topology ------------------------------------------------------------
    def bind_topology(self, topology) -> "PipelinedSync":
        super().bind_topology(topology)
        self.inner.bind_topology(topology)
        return self

    # -- ZeRO-sharded weight update (train/zero.py) --------------------------
    supports_zero = True

    def bind_zero(self, plan) -> "PipelinedSync":
        """Bind the ZeRO plan through to the wrapped algorithm: the
        inner FSA/MixedSync owns the shard-form sync, and the pipelined
        compressor double-buffers shard-sized in-flight aggregates.
        DCASGD staleness compensation is rejected: the correction term
        needs the previous step's weights at this worker's shard, and
        the host-side state init cannot address a per-worker slice — a
        full prev-params copy would forfeit the 1/W memory win the mode
        exists for."""
        if self.dcasgd_lambda > 0.0:
            raise ValueError(
                "GEOMX_ZERO does not compose with GEOMX_PIPELINE_DCASGD: "
                "the compensation's prev-params copy has no shard-local "
                "form; disable one of the two")
        # copy-bind, like the base contract: the caller's pipelined
        # instance may still drive a replicated run
        bound = copy.copy(self)
        bound.inner = self.inner.bind_zero(plan)
        bound.zero_plan = plan
        return bound

    def sync_grad_shards(self, grads: Any, params: Any, state: Any,
                         step: jax.Array) -> Tuple[Any, Any]:
        # the wrapped algorithm runs its shard-form sync; its dc-tier
        # compressor is pipelined, so the returned shards are the
        # PREVIOUS step's completed aggregates (already tier-divided)
        shards, inner_state = self.inner.sync_grad_shards(
            grads, params, state["inner"], step)
        return shards, dict(state, inner=inner_state)

    def drain_grad_shards(self, params: Any,
                          state: Any) -> Tuple[List[jax.Array], Any]:
        """ZeRO drain: the completed in-flight shard aggregates,
        tier-divided exactly as sync_grad_shards would have, with the
        buffer zeroed.  No collectives — Trainer.drain_pipeline's
        sharded program still runs the all_gather that rebuilds
        params."""
        comp = self.inner.dc_compressor
        shards, dc_state = comp.peek_shards(state["inner"]["dc_comp"])
        nl = self.num_live
        if nl > 1:
            shards = [g / nl for g in shards]
        return shards, dict(state,
                            inner=dict(state["inner"], dc_comp=dc_state))

    # -- membership (degraded-mode WAN sync, resilience/) --------------------
    def bind_membership(self, mask) -> "PipelinedSync":
        # the inner algorithm owns the masked renormalized mean; this
        # wrapper only needs the mask for its own drain divisor
        super().bind_membership(mask)
        self.inner.bind_membership(mask)
        return self

    def reset_comm_state(self, params: Any, state: Any,
                         policy: str = "reset") -> Any:
        """Membership-change policy for the pipeline: "reset" discards
        the in-flight aggregate (it was launched under the OLD
        membership — its buckets include the dead party's shard, or lack
        the re-admitted one's) along with the inner compressor's
        residuals, costing one extra warmup bubble; "carry" keeps both
        and accepts one step whose stale aggregate mixes memberships
        (renormalized by the NEW survivor count).  The DCASGD
        previous-weights copy and the model-state buffer always carry —
        both track replicated values that survive the change."""
        s = SyncAlgorithm.reset_comm_state(self, params, state, policy)
        if policy == "carry":
            return s
        inner_state = dict(s["inner"], dc_comp=self.inner._dc_init(params))
        return dict(s, inner=inner_state)

    # -- state ---------------------------------------------------------------
    def init_state(self, params: Any, model_state: Any = None) -> Any:
        state = {"inner": self.inner.init_state(params)}
        if self.dcasgd_lambda > 0.0:
            # the weights the in-flight gradient was computed at
            state["prev_params"] = jax.tree.map(jnp.asarray, params)
        if (self.num_parties > 1 and model_state is not None
                and jax.tree.leaves(model_state)):
            # seed the model-state double-buffer with the initial stats
            # (identical on every replica), not zeros: the first applied
            # buffer must be a valid BatchNorm state
            state["inflight_ms"] = jax.tree.map(jnp.asarray, model_state)
        return state

    # -- hooks ----------------------------------------------------------------
    def forward_params(self, params: Any, state: Any) -> Any:
        return self.inner.forward_params(params, state["inner"])

    def sync_grads(self, grads: Any, params: Any, state: Any,
                   step: jax.Array) -> Tuple[Any, Any]:
        # the inner algorithm runs unmodified; its dc-tier compressor is
        # pipelined, so `g` comes back as the previous step's aggregate
        # (already tier-divided by the inner algorithm)
        g, inner_state = self.inner.sync_grads(grads, params,
                                               state["inner"], step)
        new_state = dict(state, inner=inner_state)
        if self.dcasgd_lambda > 0.0:
            lam = self.dcasgd_lambda
            g = jax.tree.map(
                lambda gg, w, wp: gg + lam * gg * gg * (w - wp),
                g, params, state["prev_params"])
            # the aggregate in flight was computed at THIS step's forward
            # weights (MixedSync: its stale pull, not the true weights)
            new_state["prev_params"] = self.inner.forward_params(
                params, inner_state)
        return g, new_state

    def sync_params(self, params: Any, state: Any,
                    step: jax.Array) -> Tuple[Any, Any]:
        params, inner_state = self.inner.sync_params(params,
                                                     state["inner"], step)
        return params, dict(state, inner=inner_state)

    def sync_model_state(self, model_state: Any, state: Any,
                         step: jax.Array) -> Tuple[Any, Any]:
        if not jax.tree.leaves(model_state):
            return model_state, state
        if "inflight_ms" not in state:
            # no buffer (single party, or init_state never saw the model
            # state): keep the inner synchronous path
            ms, inner_state = self.inner.sync_model_state(
                model_state, state["inner"], step)
            return ms, dict(state, inner=inner_state)
        # both stat tiers feed the BUFFER (the applied value is the
        # previous step's fully-aggregated stats): BatchNorm aggregation
        # is one step stale as a whole, and no dc-axis result is
        # consumed in-step
        if self.workers_per_party > 1:
            model_state = lax.pmean(model_state, WORKER_AXIS)
        w = self.party_weight()
        if w is None:
            launched = lax.pmean(model_state, DC_AXIS)
        else:
            # degraded membership: the launched stat aggregate is the
            # renormalized survivor mean, same algebra as the grads
            nl = self.num_live
            launched = jax.tree.map(
                lambda x: lax.psum(x * w, DC_AXIS) / nl, model_state)
        return state["inflight_ms"], dict(state, inflight_ms=launched)

    # -- draining ------------------------------------------------------------
    def drain_grads(self, params: Any, state: Any) -> Tuple[Any, Any]:
        """The gradient tree for one drain step: the completed in-flight
        aggregate, tier-divided and compensated exactly as sync_grads
        would have, with the buffer zeroed.  No collectives — the buffer
        already holds the reduced values — so Trainer.drain_pipeline can
        run it without feeding a batch."""
        comp = self.inner.dc_compressor
        g, dc_state = comp.peek(params, state["inner"]["dc_comp"])
        nl = self.num_live  # degraded drain renormalizes over survivors
        if nl > 1:
            g = jax.tree.map(lambda x: x / nl, g)
        new_state = dict(state,
                         inner=dict(state["inner"], dc_comp=dc_state))
        if self.dcasgd_lambda > 0.0:
            lam = self.dcasgd_lambda
            g = jax.tree.map(
                lambda gg, w, wp: gg + lam * gg * gg * (w - wp),
                g, params, state["prev_params"])
        return g, new_state

    # -- telemetry (telemetry/probes.py; enabled-path only) ------------------
    def telemetry_scalars(self, state: Any) -> dict:
        """Pipeline-aware health scalars: the wrapped algorithm's EF
        residual (from the pipelined compressor's inner state, not the
        double-buffer) plus the in-flight aggregate's magnitude — a
        persistently-zero inflight norm after warmup means the pipeline
        is applying empty aggregates (exactly the silent failure a
        staleness bug produces)."""
        from geomx_tpu.telemetry.probes import tree_norm
        inner_state = state["inner"]
        dc = inner_state.get("dc_comp") if isinstance(inner_state, dict) \
            else None
        out = {}
        if isinstance(dc, dict) and "inflight" in dc:
            out["pipeline_inflight_norm"] = tree_norm(dc["inflight"])
            out["ef_residual_norm"] = tree_norm(dc.get("inner"))
        else:
            out["ef_residual_norm"] = tree_norm(dc)
        return out

    def wire_accounting(self, params: Any) -> dict:
        """The wrapped algorithm's accounting (bytes per step are
        identical — one step shifted) plus the pipeline's static shape:
        staleness and the bytes parked in flight between launch and
        apply."""
        out = self.inner.wire_accounting(params)
        out["pipeline_staleness"] = 1.0
        out["pipeline_inflight_bytes"] = out.get("dc_wire_bytes", 0.0)
        return out

    def drain_model_state(self, model_state: Any,
                          state: Any) -> Tuple[Any, Any]:
        """The model-state half of a drain step: apply the parked dc-tier
        stat aggregate (the final step's BatchNorm pmean, otherwise left
        unapplied).  The buffer keeps the applied value — identical to
        the freshly-initialized seeding, so a subsequent fit's first
        applied buffer is an identity warmup."""
        if "inflight_ms" not in state:
            return model_state, state
        parked = state["inflight_ms"]
        return parked, dict(state, inflight_ms=parked)
