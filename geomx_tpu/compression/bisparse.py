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

TPU-native design:

- Exact (or optionally TPU-approximate) top-k via ``lax.top_k`` /
  ``lax.approx_max_k`` instead of the sampled-boundary scan — the fixed
  payload size ``k = ceil(ratio*N)`` is what XLA's static shapes want, and
  it is precisely the size the reference allocates for the wire buffer.
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
from geomx_tpu.parallel.collectives import tier_scope
from geomx_tpu.utils.profiler import profile_scope

MOMENTUM = 0.9  # hardcoded in the reference (gc.cc:200)


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

    def __init__(self, ratio: float = 0.01, approx: "bool | None" = None,
                 min_sparse_size: int = 1024,
                 select: "str | None" = None,
                 fused: "bool | None" = None,
                 fused_interpret: bool = False,
                 sparse_agg: "bool | None" = None,
                 sparse_agg_parties: "int | None" = None):
        """``select``: "exact" (lax.top_k), "approx" (lax.approx_max_k),
        or "sampled" (the reference's sampled-boundary scan,
        ops/sampled_topk.py).  Default: GEOMX_BSC_SELECT if set, else —
        on a TPU with the fused kernels enabled — "sampled" (the fused
        ops/bsc_pallas.py path IS the sampled scan, as two streaming
        passes), else "approx" on TPU and "exact" elsewhere (deterministic
        behavioral tests vs the reference recurrences run on CPU).
        ``approx`` is the legacy boolean spelling of exact/approx.

        ``fused``: use the Pallas kernels (ops/bsc_pallas.py) — the
        select/pack kernel when ``select == "sampled"`` (the other
        selections keep their lax.top_k forms) and the scatter-add
        decompress for every selection.  Default: on when the backend is
        TPU and GEOMX_FUSED_KERNELS != 0.  ``fused_interpret`` runs the
        kernels in Pallas interpret mode (CPU parity tests).

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
        import os
        if ratio <= 0:
            raise ValueError("threshold must be greater than 0")
        self.ratio = float(ratio)
        from geomx_tpu.ops.bsc_pallas import fused_kernels_enabled
        if select is None:
            if approx is not None:
                select = "approx" if approx else "exact"
            else:
                # empty string (an unset-but-exported launcher variable)
                # falls back to the platform default
                # graftlint: disable=GXL006 — constructor default
                select = os.environ.get("GEOMX_BSC_SELECT") or None
            if select is None:
                if fused or (fused is None and fused_kernels_enabled()):
                    select = "sampled"
                else:
                    from geomx_tpu.compression.base import default_on_tpu
                    select = "approx" if default_on_tpu(
                        "GEOMX_BSC_APPROX_TOPK") else "exact"
        if select not in ("exact", "approx", "sampled"):
            raise ValueError(f"unknown BSC selection {select!r}")
        self.select = select
        self.approx = select == "approx"
        if fused is None:
            fused = fused_kernels_enabled()
        self.fused = bool(fused)
        # the fused select kernel implements the sampled scan only; the
        # fused decompress applies to every selection mode
        self.fused_select = self.fused and select == "sampled"
        self.fused_interpret = bool(fused_interpret)
        # tensors smaller than this aren't worth sparsifying: 2*k payload
        # would approach the dense size; send dense fp32 instead
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
        """Momentum-corrected top-k selection with error feedback.

        Returns (values[k], indices[k], new_u, new_v).

        Graft Pilot ratio retuning (control/, docs/control.md): when a
        control context is open, the EFFECTIVE selection count is
        ``eff_k = round(k * scale)`` with ``scale`` a TRACED scalar
        operand — the wire buffers stay ``k`` slots (static shapes, no
        recompile; the configured ratio is the capacity), unemitted
        slots ride as sentinels, and the unsent mass stays in the
        error-feedback buffers exactly as an under-full sampled scan
        leaves it.  With no context open (``GEOMX_CONTROL=0``) this
        method traces byte-identically to the pre-control build.
        """
        from geomx_tpu.control.actuators import current_ratio_scale
        from geomx_tpu.telemetry.probes import record_inline
        n = g_flat.shape[0]
        k = self.k_for(n)
        scale = current_ratio_scale()
        eff_k = None
        if scale is not None:
            eff_k = jnp.clip(jnp.round(k * scale), 1.0,
                             float(k)).astype(jnp.int32)
        if self.fused_select:
            # momentum math, boundary select, fixed-k pack and EF reset
            # fused (ops/bsc_pallas.py: a counting and a placing pass
            # over the bucket, one call where it is one tile); only the
            # ~8k-element threshold probe and the placement's schedule
            # run in XLA.  A traced eff_k raises the sampled boundary so
            # the kernel emits ~eff_k pairs — the kernel itself is
            # untouched (thr was always an operand).
            from geomx_tpu.ops.bsc_pallas import (bsc_select_pack,
                                                  sampled_boundary_guv,
                                                  select_pack_shape)
            with profile_scope("compress/boundary", category="kernel"):
                thr = sampled_boundary_guv(g_flat, u, v,
                                           k if eff_k is None else eff_k)
            tiles, out_blocks, _ = select_pack_shape(n, k)
            with profile_scope("bsc/select_pack", category="kernel",
                              args={"n": n, "k": k, "tiles": tiles,
                                    "out_blocks": out_blocks}):
                vals, idx, u, v = bsc_select_pack(
                    g_flat, u, v, thr, k, interpret=self.fused_interpret)
            # in-situ achieved payload (telemetry/probes.py): the
            # sampled boundary emits <= k real pairs, the rest ride as
            # sentinels — wasted wire the configured ratio hides.  The
            # thunk keeps the disabled path op-free.
            record_inline("bsc_emitted_fraction",
                          lambda: jnp.sum(idx >= 0) / k)
            return vals, idx, u, v
        u = u * MOMENTUM + g_flat
        v = v + u
        absv = jnp.abs(v)
        if self.select == "sampled":
            # the reference's own algorithm (sampled boundary + one
            # zipping scan, gc.cc:219-259) — O(n), no sort/top-k.  The
            # control plane's eff_k only moves the boundary quantile
            # (a traced gather index); the scan's shapes are untouched.
            from geomx_tpu.ops.sampled_topk import (sampled_boundary,
                                                    sampled_threshold_select)
            thr = None if eff_k is None else sampled_boundary(absv, eff_k)
            vals, idx, keep = sampled_threshold_select(v, absv, k, thr=thr)
            # error feedback: emitted coordinates reset (gc.cc:250-252)
            v = jnp.where(keep, 0.0, v)
            u = jnp.where(keep, 0.0, u)
            record_inline("bsc_emitted_fraction",
                          lambda: jnp.sum(idx >= 0) / k)
            return vals, idx, u, v
        if self.select == "approx":
            _, idx = lax.approx_max_k(absv, k)
        else:
            _, idx = lax.top_k(absv, k)
        idx = idx.astype(jnp.int32)
        if eff_k is not None:
            # ranked selection under a traced eff_k: slots past eff_k
            # become sentinels BEFORE error feedback, so the mass they
            # would have carried stays in u/v (out-of-range scatter
            # coordinates drop instead of clamping onto element n-1)
            keepslot = jnp.arange(k, dtype=jnp.int32) < eff_k
            vals = jnp.where(keepslot, v[idx], 0.0)
            sent = jnp.where(keepslot, idx, n).astype(jnp.int32)
            v = v.at[sent].set(0.0, mode="drop")
            u = u.at[sent].set(0.0, mode="drop")
            out_idx = jnp.where(keepslot, idx, -1).astype(jnp.int32)
            record_inline("bsc_emitted_fraction",
                          lambda: jnp.sum(out_idx >= 0) / k)
            return vals, out_idx, u, v
        vals = v[idx]
        # error feedback: sent coordinates reset in both buffers (gc.cc:250-252)
        v = v.at[idx].set(0.0)
        u = u.at[idx].set(0.0)
        # exact/approx top-k always fills all k slots
        record_inline("bsc_emitted_fraction", lambda: jnp.ones((), jnp.float32))
        return vals, idx, u, v

    def decompress(self, vals: jax.Array, idx: jax.Array, n: int) -> jax.Array:
        """Scatter-add (value, index) pairs into a dense vector
        (reference BSCDecompress, gc.cc:310-336). Negative indices are
        padding sentinels and are dropped."""
        if self.fused:
            # fused scatter-add: no XLA scatter, no per-party dense
            # intermediate (ops/bsc_pallas.py)
            from geomx_tpu.ops.bsc_pallas import bsc_scatter_add
            with profile_scope("bsc/scatter_add", category="kernel",
                              args={"n": n, "pairs": int(vals.shape[0])}):
                return bsc_scatter_add(vals, idx, n,
                                       interpret=self.fused_interpret)
        valid = idx >= 0
        safe_idx = jnp.where(valid, idx, 0)
        contrib = jnp.where(valid, vals, 0.0)
        return jnp.zeros((n,), jnp.float32).at[safe_idx].add(contrib)

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
                    ef_buffer=v, merge_fused=self.fused,
                    interpret=self.fused_interpret)
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
