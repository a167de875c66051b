"""FSA — Fully Synchronous Algorithm (the reference's dist_sync default).

Reference dataflow (SURVEY.md §3.3): every step, workers push gradients to
their local PS; the local tier is pure aggregation (ApplyUpdates with no
updater, kvstore_dist_server.h:502-523); local servers push the merged
gradient to the global tier, which runs the optimizer once all parties
arrive (kvstore_dist_server.h:1305-1318); fresh weights flow back down.

TPU-native: one hierarchical compressed all-reduce per step —

    g_party  = psum(g, "worker") / workers_per_party      (ICI tier)
    g_global = dc_compressor.allreduce(g_party, "dc") / P (DCN tier)

followed by an optimizer step applied identically on every device, which
keeps parameters replicated without any explicit pull.  The dc-tier
compressor slot is where Bi-Sparse / FP16 / MPQ / 2-bit plug in, exactly
the hop they compress in the reference (local server -> global server).
By default the dc compressor is wrapped in the bucketed communication
engine (compression/bucketing.py): the gradient tree fuses into a few
flat fp32 buckets, one compressed collective each, instead of one
collective per leaf (GEOMX_BUCKET_BYTES=0 opts out).  An optional
worker-tier compressor covers the reference's intra-DC fp16 mode.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
from jax import lax

from geomx_tpu.compression.base import Compressor, NoCompressor
from geomx_tpu.parallel.collectives import tier_scope
from geomx_tpu.utils.profiler import profile_scope
from geomx_tpu.sync.base import SyncAlgorithm
from geomx_tpu.topology import DC_AXIS, WORKER_AXIS


class FSA(SyncAlgorithm):
    name = "fsa"
    supports_degraded = True  # renormalized survivor mean (resilience/)
    grads_replicated_after_sync = True  # hierarchical psum output
    supports_zero = True  # bucket-shard form of the same hierarchy

    def __init__(self, dc_compressor: Optional[Compressor] = None,
                 worker_compressor: Optional[Compressor] = None,
                 bucket_bytes: Optional[int] = None):
        from geomx_tpu.compression.bucketing import maybe_bucketed
        # the dc tier pays a fixed DCN round trip per collective, so the
        # default path fuses the gradient tree into a few flat buckets
        # (one compressed collective each); GEOMX_BUCKET_BYTES=0 or
        # bucket_bytes=0 restores the per-leaf path.  The ICI-tier worker
        # compressor stays per-leaf — intra-DC latency doesn't warrant
        # the re-layout.
        self.dc_compressor = maybe_bucketed(dc_compressor or NoCompressor(),
                                            bucket_bytes)
        self.worker_compressor = worker_compressor or NoCompressor()

    def _dc_init(self, params: Any) -> Any:
        """dc-tier compressor state: shard-shaped under a bound ZeRO
        plan (EF residuals live on this worker's 1/W bucket slice),
        bucket/leaf-shaped otherwise."""
        if self.zero_plan is not None:
            return self.dc_compressor.init_shard_state(params,
                                                       self.zero_plan.W)
        return self.dc_compressor.init_state(params)

    def init_state(self, params: Any, model_state: Any = None) -> Any:
        return {
            "dc_comp": self._dc_init(params),
            "worker_comp": self.worker_compressor.init_state(params),
        }

    def sync_grads(self, grads: Any, params: Any, state: Any,
                   step: jax.Array) -> Tuple[Any, Any]:
        nw = self.workers_per_party
        np_ = self.num_parties
        # intra-party tier (ICI): mean over workers
        g, wstate = self.worker_compressor.allreduce(
            grads, state["worker_comp"], WORKER_AXIS, nw)
        if nw > 1:  # single-worker parties skip the dead x/1 divide
            g = jax.tree.map(lambda x: x / nw, g)
        # degraded mode: a dead party's shard is excluded (multiplied to
        # exact zeros before the collective) and the mean renormalizes
        # over the num_live survivors — for live parties the aggregate
        # is bit-identical to the mean over survivors alone
        w = self.party_weight()
        if w is not None:
            g = jax.tree.map(lambda x: x * w, g)
        # cross-party tier (DCN): compressed mean over parties
        g, dstate = self.dc_compressor.allreduce(g, state["dc_comp"], DC_AXIS, np_)
        nl = self.num_live
        if nl > 1:
            g = jax.tree.map(lambda x: x / nl, g)
        return g, {"dc_comp": dstate, "worker_comp": wstate}

    def sync_grad_shards(self, grads: Any, params: Any, state: Any,
                         step: jax.Array) -> Tuple[Any, Any]:
        """ZeRO form of :meth:`sync_grads` (train/zero.py): the same
        two-tier hierarchy on 1/W bucket shards —

            worker tier: psum_scatter(flat buckets) / W   (ICI)
            dc tier:     compressed allreduce per SHARD   (DCN)

        Each chip compresses, transfers, decompresses and (in
        train/step.py) updates only its contiguous shard of every fused
        bucket; the degraded-membership renormalization applies on the
        shards with the identical survivor-mean algebra.  Returns the
        list of global-mean bucket shards, not a gradient tree."""
        plan = self.zero_plan
        leaves = jax.tree.leaves(grads)
        bk = self.dc_compressor.zero_bucketer(leaves)
        # worker tier: the scatter IS the reduce (and a 1/W wire saving
        # per ICI link); a configured worker compressor is bypassed —
        # build_train_step warns, mirroring MultiGPS
        with profile_scope("compress/flatten"):
            buckets = bk.flatten(leaves)
        with tier_scope(WORKER_AXIS):
            shards = [plan.scatter_bucket(b, WORKER_AXIS) for b in buckets]
        w = self.party_weight()
        if w is not None:
            # degraded mode: identical exclusion algebra to sync_grads,
            # applied shard-wise — a dead party's shard zeroes before
            # the collective and the mean renormalizes over survivors
            shards = [x * w for x in shards]
        shards, dstate = self.dc_compressor.allreduce_shards(
            shards, state["dc_comp"], DC_AXIS, self.num_parties, bk)
        nl = self.num_live
        if nl > 1:
            shards = [x / nl for x in shards]
        return shards, dict(state, dc_comp=dstate)

    def sync_model_state(self, model_state: Any, state: Any,
                         step: jax.Array) -> Tuple[Any, Any]:
        # keep non-trainable stats (BatchNorm) consistent across replicas
        if self.workers_per_party > 1:
            with tier_scope(WORKER_AXIS):
                model_state = lax.pmean(model_state, WORKER_AXIS)
        if self.num_parties > 1:
            w = self.party_weight()
            with tier_scope(DC_AXIS):
                if w is None:
                    model_state = lax.pmean(model_state, DC_AXIS)
                else:
                    # renormalized survivor mean, same algebra as the
                    # grads
                    nl = self.num_live
                    model_state = jax.tree.map(
                        lambda x: lax.psum(x * w, DC_AXIS) / nl,
                        model_state)
        return model_state, state

    def reset_comm_state(self, params: Any, state: Any,
                         policy: str = "reset") -> Any:
        """Membership-change policy: "reset" re-initializes the dc-tier
        compressor state (error-feedback residuals accumulated against
        the old membership would replay a dead party's history into the
        renormalized mean); the worker tier is untouched — intra-party
        membership did not change."""
        state = super().reset_comm_state(params, state, policy)
        if policy == "carry":
            return state
        return dict(state, dc_comp=self._dc_init(params))

    def telemetry_scalars(self, state: Any) -> dict:
        """EF-residual magnitude of the dc-tier compressor state (the
        momentum/velocity buffers a sparse compressor holds back): the
        in-situ "how much gradient mass is parked in error feedback"
        signal (telemetry/probes.py; enabled-path only)."""
        from geomx_tpu.telemetry.probes import tree_norm
        return {"ef_residual_norm": tree_norm(state["dc_comp"])}
