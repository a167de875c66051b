"""HFA — Hierarchical Frequency Aggregation.

Reference semantics (README.md:41-44; worker loop examples/cnn_hfa.py:108-134;
server milestone math kvstore_dist_server.h:988-1017,1327-1346):

- every step: each worker runs its *own* optimizer update (params drift);
- every K1 steps: workers push ``params / num_local_workers`` and pull — the
  local tier averages parameters within the party;
- every K2 local syncs (i.e. every K1*K2 steps): the local server pushes
  ``(store - milestone) / num_parties`` — the parameter *delta* since the
  last global milestone — the global server sets
  ``store = milestone + sum(deltas)`` and everyone resets their milestone.

Net effect: two-frequency hierarchical parameter averaging.  The milestone
is not redundant once the global delta is compressed (Bi-Sparse over HFA):
unsent delta mass stays in the compressor residuals relative to the
milestone, exactly as in the reference's compressed-HFA path
(kvstore_dist_server.h:1334-1338).

TPU-native: parameters live per-device (replica axes), the K1 hook is a
``pmean`` over the worker axis, the K1*K2 hook a compressed all-reduce of
deltas over the dc axis, both gated by ``lax.cond`` so skipped steps cost
nothing on the wire.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from geomx_tpu.compression.base import Compressor, NoCompressor
from geomx_tpu.sync.base import SyncAlgorithm
from geomx_tpu.topology import DC_AXIS, WORKER_AXIS


class HFA(SyncAlgorithm):
    name = "hfa"

    def __init__(self, k1: int = 20, k2: int = 10,
                 dc_compressor: Optional[Compressor] = None,
                 bucket_bytes: Optional[int] = None):
        if k1 < 1 or k2 < 1:
            raise ValueError("HFA periods must be >= 1")
        from geomx_tpu.compression.bucketing import maybe_bucketed
        self.k1 = int(k1)
        self.k2 = int(k2)
        # the K1*K2 global delta crosses the same WAN hop as FSA's
        # gradients, so it gets the same fused flat-bucket default: one
        # compressed collective per bucket instead of per leaf
        # (GEOMX_BUCKET_BYTES=0 opts out).  Exact for the dense default
        # (the bucket layout is a permutation and the padding is zeros).
        self.dc_compressor = maybe_bucketed(dc_compressor or NoCompressor(),
                                            bucket_bytes)

    def init_state(self, params: Any, model_state: Any = None) -> Any:
        if self.num_parties <= 1:
            # one party: the global tier never fires (the Python gate in
            # sync_params), so a milestone copy + compressor state would
            # be dead weight threaded through every dispatch
            return {}
        return {
            # last globally-agreed parameters (reference stored_milestone)
            "milestone": jax.tree.map(jnp.asarray, params),
            "dc_comp": self.dc_compressor.init_state(params),
        }

    # gradients are applied locally — no per-step gradient communication
    # (that is the point of HFA: sync frequency decoupled from step frequency)

    def sync_params(self, params: Any, state: Any,
                    step: jax.Array) -> Tuple[Any, Any]:
        # `step` is the 0-based step being finished; the reference gates on
        # 1-based global_iters % K1 == 0 (cnn_hfa.py:119)
        iters = step + 1
        do_local = (iters % self.k1) == 0
        do_global = (iters % (self.k1 * self.k2)) == 0

        if self.workers_per_party > 1:
            def local_sync(p):
                return lax.pmean(p, WORKER_AXIS)
            params = lax.cond(do_local, local_sync, lambda p: p, params)

        def global_sync(operand):
            p, st = operand
            milestone = st["milestone"]
            # per-party delta, pre-divided as the reference does
            # ((store - milestone)/NumGlobalWorkers, kvstore_dist_server.h:1334)
            delta = jax.tree.map(
                lambda a, m: (a - m) / self.num_parties, p, milestone)
            agg, comp_state = self.dc_compressor.allreduce(
                delta, st["dc_comp"], DC_AXIS, self.num_parties)
            new_p = jax.tree.map(lambda m, d: m + d, milestone, agg)
            return new_p, {"milestone": new_p, "dc_comp": comp_state}

        def no_global(operand):
            p, st = operand
            return p, st

        if self.num_parties > 1:
            params, state = lax.cond(do_global, global_sync, no_global,
                                     (params, state))
        return params, state

    def sync_model_state(self, model_state: Any, state: Any,
                         step: jax.Array) -> Tuple[Any, Any]:
        if not jax.tree.leaves(model_state):
            return model_state, state
        iters = step + 1
        if self.workers_per_party > 1:
            model_state = lax.cond(
                (iters % self.k1) == 0,
                lambda s: lax.pmean(s, WORKER_AXIS), lambda s: s, model_state)
        if self.num_parties > 1:
            model_state = lax.cond(
                (iters % (self.k1 * self.k2)) == 0,
                lambda s: lax.pmean(s, DC_AXIS), lambda s: s, model_state)
        return model_state, state
