"""Bi-directional Sparse (Bi-Sparse / "bsc") gradient compression.

Reference semantics (src/kvstore/gradient_compression.cc:191-336):

- *Push side* (local server -> global server, BSCompress): DGC-style
  momentum correction ``u = 0.9*u + g; v = v + u``; pick a magnitude
  boundary so that ~``ratio`` of elements survive (the reference estimates
  the boundary from a random sample of 0.5% of elements); emit exactly
  ``ceil(ratio*N)`` (value, index) pairs padded with sentinels
  (-65530 / -1, gc.cc:257-259); zero u and v at the sent positions
  (error feedback).
- *Pull side* (global server -> local server, BSCPullCompress): the
  aggregated tensor has at most ``k * num_parties`` non-zeros; transmit
  only those, again as fixed-size (value, index) pairs — so the pull is
  sparse too ("bi-directional").

Design here:

- The selection is the reference's own: a magnitude boundary from a
  sorted probe of ~8k fixed positions (``ops/bsc_pallas.
  sampled_boundary_guv``), then one two-tier scan (elements strictly
  above the boundary claim slots first, in index order; boundary ties
  fill what remains).  It emits AT MOST ``k = ceil(ratio*N)`` pairs,
  ~97% of k at BERT-large's sizes; unfilled slots ride as sentinels and
  the unsent mass stays in u/v.  O(N), no sort of anything the bucket's
  size.  There is no other rule: this is what the chip runs, what the
  tests run, and what the benchmark's plain reference implements.
- This module says what is sent, what is kept back and what it costs on
  the wire.  How select/pack and the scatter-add are computed (a Pallas
  kernel on a TPU, their jnp forms elsewhere) is ``ops/dispatch.py``'s
  decision, made from the platform; nothing here can override it.
- The all-gather of the (values, indices) pairs across the ``dc`` axis is
  the push; every party scatter-adds all parties' pairs into a dense
  aggregate locally. Because the aggregate has <= k*P non-zeros by
  construction, this dense reconstruction carries exactly the information
  of the reference's sparse pull — no second truncation happens on pull
  (multiplier semantics of BSCPullCompress, gc.cc:277).
- Wire cost: 2 * k floats per party per sync, matching the reference's
  ``zipped_size * 2`` payload.
"""

from __future__ import annotations

import math
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from geomx_tpu.compression.base import Compressor
from geomx_tpu.ops import dispatch
from geomx_tpu.ops.bsc_pallas import select_pack_shape
from geomx_tpu.parallel.collectives import tier_scope
from geomx_tpu.utils.profiler import profile_scope

def _note_dense_fallback(n: int, min_sparse_size: int) -> None:
    """The silent "too small to sparsify, send dense fp32" decision,
    made observable: one counter bump + one debug line per TRACE of a
    falling-back leaf/bucket (the decision is static per shape — a
    per-step count would just multiply it by the step count), so MPQ /
    Graft Pilot tuning can see when sparsification is being bypassed."""
    import logging

    from geomx_tpu.telemetry import get_registry
    # graftlint: disable=GXL004 — per-trace (static-shape) accounting
    get_registry().counter(
        "geomx_bsc_dense_fallback_total",
        "BSC leaves/buckets sent dense fp32 instead of sparsified",
        ("reason",)).labels("below_min_sparse_size").inc()
    logging.getLogger("geomx_tpu.compression").debug(
        "bsc dense fallback: leaf of %d elements < min_sparse_size=%d "
        "— 2k-pair payload would approach dense size, sending dense fp32",
        n, min_sparse_size)


class BiSparseCompressor(Compressor):
    name = "bsc"

    def __init__(self, ratio: float = 0.01, min_sparse_size: int = 1024,
                 sparse_agg: "bool | None" = None,
                 sparse_agg_parties: "int | None" = None):
        """``min_sparse_size``: tensors smaller than this aren't worth
        sparsifying (the 2*k payload would approach the dense size) and
        go dense fp32.

        ``sparse_agg``: merge in the compressed domain — the
        owner-routed sparse allreduce of compression/sparseagg.py
        (route pairs to index-range owners over ``all_to_all``, merge
        by sorted-index segment sum, re-select per owner, one final
        decompress) instead of the all-gather + dense scatter-add
        chain.  Per-chip wire and merge work become O(k) instead of
        O(k * parties); the merged result carries the pull-side
        re-selection budget (``GEOMX_SPARSE_AGG_PULL_SLACK`` * k pairs
        globally), with push-routing overflow reinjected into the
        error-feedback velocity.  Default: ``GEOMX_SPARSE_AGG``
        (off).  ``sparse_agg_parties`` pins the dc-axis width the
        owner-routed path's wire accounting assumes; without it the
        width of the most recent traced allreduce is used (2 before
        any trace) — pass it when calling ``wire_bytes`` before the
        first trace or when one instance serves multiple widths."""
        if ratio <= 0:
            raise ValueError("threshold must be greater than 0")
        self.ratio = float(ratio)
        self.min_sparse_size = int(min_sparse_size)
        if sparse_agg is None:
            from geomx_tpu.compression.sparseagg import sparse_agg_enabled
            sparse_agg = sparse_agg_enabled()
        self.sparse_agg = bool(sparse_agg)
        # dc-axis width the owner-routed wire accounting assumes: the
        # explicit pin when given, else the width of the last traced
        # allreduce (2 before any trace) — the payload depends on the
        # party count
        self.sparse_agg_parties = None if sparse_agg_parties is None \
            else int(sparse_agg_parties)
        self._wire_axis_size = self.sparse_agg_parties or 2

    def k_for(self, n: int) -> int:
        return max(1, int(math.ceil(n * self.ratio)))

    def _sparse_eligible(self, n: int) -> bool:
        return n >= self.min_sparse_size

    def init_leaf_state(self, leaf: jax.Array) -> Any:
        if not self._sparse_eligible(leaf.size):
            return ()
        # momentum buffer u and velocity (error accumulator) v, gc.cc:219-222
        return (jnp.zeros(leaf.shape, jnp.float32),
                jnp.zeros(leaf.shape, jnp.float32))

    def compress(self, g_flat: jax.Array, u: jax.Array, v: jax.Array):
        """Momentum-corrected sampled-boundary selection with error
        feedback.  Returns (values[k], indices[k], new_u, new_v).

        Graft Pilot ratio retuning (control/, docs/control.md): when a
        control context is open, the boundary is the one that lets
        ``eff_k = round(k * scale)`` pairs through, ``scale`` a TRACED
        scalar operand — the wire buffers stay ``k`` slots (static
        shapes, no recompile; the configured ratio is the capacity),
        unemitted slots ride as sentinels, and the unsent mass stays in
        the error-feedback buffers.  With no context open
        (``GEOMX_CONTROL=0``) the boundary's position is static.
        """
        from geomx_tpu.control.actuators import current_ratio_scale
        from geomx_tpu.telemetry.probes import record_inline
        n = g_flat.shape[0]
        k = self.k_for(n)
        scale = current_ratio_scale()
        eff_k = k
        if scale is not None:
            eff_k = jnp.clip(jnp.round(k * scale), 1.0,
                             float(k)).astype(jnp.int32)
        with profile_scope("compress/boundary", category="kernel"):
            thr = dispatch.sampled_boundary(g_flat, u, v, eff_k)
        tiles, out_blocks, _ = select_pack_shape(n, k)
        with profile_scope("bsc/select_pack", category="kernel",
                           args={"n": n, "k": k, "tiles": tiles,
                                 "out_blocks": out_blocks}):
            vals, idx, u, v = dispatch.select_pack(g_flat, u, v, thr, k)
        # in-situ achieved payload (telemetry/probes.py): the sampled
        # boundary emits <= k real pairs, the rest ride as sentinels —
        # wasted wire the configured ratio hides.  The thunk keeps the
        # disabled path op-free.
        record_inline("bsc_emitted_fraction", lambda: jnp.sum(idx >= 0) / k)
        return vals, idx, u, v

    def decompress(self, vals: jax.Array, idx: jax.Array, n: int) -> jax.Array:
        """Scatter-add (value, index) pairs into a dense vector
        (reference BSCDecompress, gc.cc:310-336). Negative indices are
        padding sentinels and are dropped."""
        with profile_scope("bsc/scatter_add", category="kernel",
                           args={"n": n, "pairs": int(vals.shape[0])}):
            return dispatch.scatter_add(vals, idx, n)

    def allreduce_leaf(self, g: jax.Array, state: Any, axis_name: str,
                       axis_size: int) -> Tuple[jax.Array, Any]:
        shape, dtype, n = g.shape, g.dtype, g.size
        if not self._sparse_eligible(n):
            _note_dense_fallback(n, self.min_sparse_size)
            if axis_size == 1:
                return g, state
            with profile_scope("compress/exchange", category="comm"), \
                    tier_scope(axis_name):
                return lax.psum(g, axis_name), state
        u, v = state
        vals, idx, u, v = self.compress(
            g.reshape(-1).astype(jnp.float32), u.reshape(-1), v.reshape(-1))
        if axis_size == 1:
            with profile_scope("compress/merge", category="kernel"):
                out = self.decompress(vals, idx, n)
        elif self.sparse_agg:
            # compressed-domain merge (compression/sparseagg.py): route
            # pairs to their index-range owners, merge by sorted-index
            # segment sum, re-select per owner, decompress ONCE.  The
            # routing overflow (pairs past a destination's slot budget)
            # reinjects into the error-feedback velocity so its mass
            # retries next round instead of vanishing.
            from geomx_tpu.compression.sparseagg import sparse_allreduce
            if self.sparse_agg_parties is None:
                self._wire_axis_size = int(axis_size)
            # its routing and its merge interleave: one scope for both
            with profile_scope("compress/exchange", category="comm"):
                out, v = sparse_allreduce(
                    vals, idx, n, axis_name, axis_size, self.decompress,
                    ef_buffer=v)
        else:
            # the wire transfer: 2k floats per party over the dc tier
            with profile_scope("compress/exchange", category="comm"), \
                    tier_scope(axis_name):
                all_vals = lax.all_gather(vals, axis_name).reshape(-1)
                all_idx = lax.all_gather(idx, axis_name).reshape(-1)
            with profile_scope("compress/merge", category="kernel"):
                out = self.decompress(all_vals, all_idx, n)
        return (out.reshape(shape).astype(dtype),
                (u.reshape(shape), v.reshape(shape)))

    def wire_bytes_leaf(self, leaf: jax.Array) -> int:
        n = leaf.size
        if not self._sparse_eligible(n):
            return n * 4
        if self.sparse_agg:
            from geomx_tpu.compression.sparseagg import sparse_wire_bytes
            return sparse_wire_bytes(self.k_for(n), self._wire_axis_size)
        return 2 * self.k_for(n) * 4
