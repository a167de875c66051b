"""Bucketed flat-gradient communication: fuse per-leaf collectives.

Every compressed sync tier used to launch one collective per pytree leaf
(``Compressor.allreduce`` loops leaves), so a model with hundreds of
parameters paid hundreds of fixed DCN round-trip latencies per step on
the WAN tier.  ``GradientBucketer`` flattens the gradient pytree into a
few contiguous fp32 buckets with a *static* layout (leaf -> (bucket,
offset, size), computed once per tree structure at trace time), and
``BucketedCompressor`` runs the wrapped compressor once per bucket — one
top-k / one quantize / one gather per bucket instead of per leaf,
matching the O(k) fused-allreduce structure of Near-Optimal Sparse
Allreduce (arXiv:2201.07598) and EQuARX's fused quantized collectives
(arXiv:2506.17615).

Semantics by inner compressor:

- dense / fp16 / 2bit are element-wise, so the bucketed path is
  numerically identical to the per-leaf path (the layout is a pure
  permutation and zero padding quantizes/accumulates to nothing);
- BSC's top-k becomes a *global* selection over each bucket: k =
  ceil(ratio * bucket_elems) slots are allocated where the magnitude
  actually lives instead of per-leaf quotas (DGC-style global ranking —
  strictly better value-per-byte at the same wire size);
- MPQ routes small-vs-large at *bucket* granularity: a bucket of many
  small leaves crosses ``size_lower_bound`` as one tensor and earns the
  sparse path its members would each have missed.

Error-feedback state (residuals, momentum/velocity) lives on the bucket
layout itself, so it round-trips exactly: what the per-leaf path kept in
N leaf-shaped buffers the bucketed path keeps in one flat buffer per
bucket, with identical mass at the same (leaf, offset) coordinates.

Buckets are padded to a lane-friendly multiple (default 128, the TPU
lane width; also a multiple of the 2-bit packer's 16-codes-per-word) so
the fused kernels see aligned shapes.  ``GEOMX_BUCKET_BYTES`` sets the
bucket capacity (default 4 MiB of fp32); ``GEOMX_BUCKET_BYTES=0`` opts
out and restores the per-leaf path.

Buckets are always fp32 (the accumulation dtype every inner compressor
computes in; this framework's models keep fp32 params/grads with bf16
compute, so the sync tiers see fp32 leaves).  A tree of 16-bit
*gradients* would upcast on the bucketed dense path — wire accounting
reports the real fp32 payload honestly; to keep a 2-byte wire there,
use an fp16/bf16 inner compressor (its gather is 16-bit regardless of
the bucket dtype), or opt out with ``GEOMX_BUCKET_BYTES=0``.
"""

from __future__ import annotations

import os
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from geomx_tpu.compression.base import Compressor
from geomx_tpu.ops import dispatch
from geomx_tpu.utils.profiler import profile_scope

# 4 MiB of fp32 per bucket: large enough that a ResNet/transformer
# collapses to a handful of collectives, small enough that compress /
# gather / decompress pipeline across buckets.
DEFAULT_BUCKET_BYTES = 4 * 1024 * 1024

_LANE_PAD = 128  # TPU lane width; multiple of the 2-bit 16-codes word


def _bucket_leaf(n: int) -> jax.ShapeDtypeStruct:
    """Abstract stand-in for a flat fp32 bucket, for state init and wire
    accounting (init_leaf_state/wire_bytes_leaf only read shape/size/
    dtype)."""
    return jax.ShapeDtypeStruct((n,), jnp.float32)


class GradientBucketer:
    """Static flat layout of a leaf sequence into contiguous fp32 buckets.

    The layout is computed once from abstract leaves (shape + dtype) and
    is pure Python — inside ``jit`` it resolves at trace time, so the
    flatten/unflatten below lower to concatenates and slices with static
    offsets (no gather, no dynamic shapes).

    Packing is greedy in flatten order: leaves fill the current bucket
    until capacity, then a new bucket opens; a leaf larger than the
    capacity gets a bucket of its own (leaves are never split, so every
    leaf is contiguous in exactly one bucket).
    """

    def __init__(self, leaves: Sequence[Any],
                 bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                 pad_to: int = _LANE_PAD):
        if bucket_bytes <= 0:
            raise ValueError(f"bucket_bytes must be > 0, got {bucket_bytes}")
        self.pad_to = max(1, int(pad_to))
        self.capacity = max(self.pad_to, int(bucket_bytes) // 4)
        self.leaf_shapes = [tuple(leaf.shape) for leaf in leaves]
        self.leaf_dtypes = [jnp.dtype(leaf.dtype) for leaf in leaves]
        self.leaf_sizes = [int(leaf.size) for leaf in leaves]

        # leaf -> (bucket, offset); bucket -> true fill
        self.assignments: List[Tuple[int, int]] = []
        fills: List[int] = []
        for size in self.leaf_sizes:
            if fills and fills[-1] > 0 and fills[-1] + size > self.capacity:
                fills.append(0)
            if not fills:
                fills.append(0)
            self.assignments.append((len(fills) - 1, fills[-1]))
            fills[-1] += size
        self.bucket_fill = fills if self.leaf_sizes else []
        # lane-friendly padded bucket lengths (zero-filled tails)
        self.bucket_sizes = [-(-f // self.pad_to) * self.pad_to
                             for f in self.bucket_fill]

    @property
    def num_buckets(self) -> int:
        return len(self.bucket_sizes)

    def _layout(self) -> tuple:
        """leaf -> (bucket, offset, size) triples (static, hashable) for
        the fused DMA kernels."""
        return tuple((b, off, size) for (b, off), size in
                     zip(self.assignments, self.leaf_sizes))

    def flatten(self, leaves: Sequence[jax.Array]) -> List[jax.Array]:
        """Pytree leaves -> list of flat fp32 buckets (padded): on a TPU
        one Pallas DMA kernel per multi-leaf bucket, elsewhere one XLA
        concatenate operand per leaf (ops/dispatch.py decides)."""
        if not self.num_buckets:
            return []
        flat = [leaf.reshape(-1).astype(jnp.float32) for leaf in leaves]
        return dispatch.flatten_buckets(flat, self._layout(),
                                        tuple(self.bucket_sizes))

    def unflatten(self, buckets: Sequence[jax.Array]) -> List[jax.Array]:
        """Flat buckets -> leaves with their original shapes and dtypes."""
        if not self.num_buckets:
            return []
        flat = dispatch.unflatten_buckets(
            [b.reshape(-1) for b in buckets], self._layout(),
            tuple(self.leaf_sizes))
        return [f.reshape(shape).astype(dtype)
                for f, shape, dtype in zip(flat, self.leaf_shapes,
                                           self.leaf_dtypes)]


def _resolve_bucket_bytes(bucket_bytes: Optional[int]) -> int:
    if bucket_bytes is not None:
        return int(bucket_bytes)
    # graftlint: disable=GXL006 — constructor default
    raw = os.environ.get("GEOMX_BUCKET_BYTES")
    if raw:
        return int(float(raw))
    return DEFAULT_BUCKET_BYTES


class BucketedCompressor(Compressor):
    """Run ``inner`` once per fused bucket instead of once per leaf.

    Satisfies the ``Compressor`` interface, so every existing algorithm
    (``none``, ``fp16``, ``2bit``, ``bsc``, ``mpq``) gains the fused path
    without a per-algorithm rewrite.  ``init_state``/``allreduce`` are
    tree-level: state is a list of per-bucket inner states living on the
    flat bucket layout.  ``name`` mirrors the inner compressor so wire
    accounting and config checks stay transparent.
    """

    fuses_tree = True  # already one-per-bucket: never wrap again

    def __init__(self, inner: Compressor,
                 bucket_bytes: Optional[int] = None,
                 pad_to: int = _LANE_PAD):
        self.inner = inner
        self.name = inner.name
        self.bucket_bytes = _resolve_bucket_bytes(bucket_bytes)
        if self.bucket_bytes <= 0:
            raise ValueError("BucketedCompressor needs bucket_bytes > 0; "
                             "use the bare inner compressor to disable "
                             "bucketing")
        self.pad_to = pad_to
        self._bucketers: dict = {}

    # -- layout cache (one per tree structure, resolved at trace time) ------
    def _bucketer(self, leaves: Sequence[Any]) -> GradientBucketer:
        key = tuple((tuple(leaf.shape), jnp.dtype(leaf.dtype).str) for leaf in leaves)
        bk = self._bucketers.get(key)
        if bk is None:
            bk = GradientBucketer(leaves, self.bucket_bytes, self.pad_to)
            self._bucketers[key] = bk
        return bk

    # -- state --------------------------------------------------------------
    def init_state(self, grads: Any) -> Any:
        leaves = jax.tree.leaves(grads)
        bk = self._bucketer(leaves)
        return [self.inner.init_leaf_state(_bucket_leaf(n))
                for n in bk.bucket_sizes]

    def init_leaf_state(self, leaf: jax.Array) -> Any:
        bk = self._bucketer([leaf])
        return self.inner.init_leaf_state(_bucket_leaf(bk.bucket_sizes[0]))

    # -- the fused all-reduce ------------------------------------------------
    def allreduce_buckets(self, buckets: Sequence[jax.Array], state: Any,
                          axis_name: str, axis_size: int,
                          bk: GradientBucketer) -> Tuple[List[jax.Array], Any]:
        """One compressed collective per flat bucket; the layer the
        pipelined engine (sync/pipeline.py) calls directly so its
        in-flight double-buffer can live on the bucket layout without a
        re-flatten round trip."""
        if len(state) != bk.num_buckets:
            raise ValueError(
                f"bucketed state has {len(state)} buckets but the gradient "
                f"layout needs {bk.num_buckets} — state was initialized "
                "from a different tree (init_state and allreduce must see "
                "the same pytree structure)")
        out_buckets, new_states = [], []
        for i, (b, s) in enumerate(zip(buckets, state)):
            # the bucket's ops carry this label in their op names; the
            # host span and its payload size are of the trace of the
            # step, not of its run (utils/profiler.py)
            with profile_scope(
                    f"{axis_name}_allreduce/bucket{i}", category="comm",
                    args={"bucket": i, "elems": bk.bucket_fill[i],
                          "padded": bk.bucket_sizes[i],
                          "payload_bytes": self.inner.wire_bytes_leaf(
                              _bucket_leaf(bk.bucket_sizes[i]))}):
                ob, ns = self.inner.allreduce_leaf(b, s, axis_name,
                                                   axis_size)
            out_buckets.append(ob)
            new_states.append(ns)
        return out_buckets, new_states

    def allreduce(self, grads: Any, state: Any, axis_name: str,
                  axis_size: int) -> Tuple[Any, Any]:
        leaves, treedef = jax.tree.flatten(grads)
        if not leaves:
            return grads, state
        bk = self._bucketer(leaves)
        with profile_scope("compress/flatten"):
            buckets = bk.flatten(leaves)
        out_buckets, new_states = self.allreduce_buckets(
            buckets, state, axis_name, axis_size, bk)
        with profile_scope("compress/unflatten"):
            return treedef.unflatten(bk.unflatten(out_buckets)), new_states

    # -- the ZeRO shard view (train/zero.py) ---------------------------------
    def zero_bucketer(self, leaves: Sequence[Any]) -> GradientBucketer:
        """The bucket layout the ZeRO path shards: same cache as the
        replicated path (one layout per tree structure), exposed so the
        sync algorithms and train/step.py slice identical coordinates."""
        return self._bucketer(leaves)

    def init_shard_state(self, grads: Any, num_shards: int) -> Any:
        """Per-bucket inner state sized for one contiguous ``1/W`` bucket
        shard — the ZeRO form of :meth:`init_state`.  Error-feedback
        residuals (BSC momentum/velocity) live shard-local: each chip
        accumulates feedback only for the coordinates it owns, so the
        state memory drops by W exactly like the optimizer's.  Requires
        ``pad_to`` to be a multiple of ``num_shards`` times the lane
        width (ZeroPlan.bind_compressor sets it)."""
        leaves = jax.tree.leaves(grads)
        bk = self._bucketer(leaves)
        for n in bk.bucket_sizes:
            if n % num_shards:
                raise ValueError(
                    f"bucket of {n} elements does not split into "
                    f"{num_shards} equal shards — the ZeRO path needs "
                    "pad_to to be a multiple of num_shards*lane "
                    "(ZeroPlan.bind_compressor sets this before the "
                    "first trace)")
        return [self.inner.init_leaf_state(_bucket_leaf(n // num_shards))
                for n in bk.bucket_sizes]

    def allreduce_shards(self, shards: Sequence[jax.Array], state: Any,
                         axis_name: str, axis_size: int,
                         bk: GradientBucketer) -> Tuple[List[jax.Array], Any]:
        """One compressed collective per 1/W bucket *shard* — the ZeRO
        dc tier.  Each chip compresses and transfers only its shard, so
        no party ever materializes a bucket-dense intermediate on the
        compressed path (the Ok-Topk property) and the per-link payload
        drops by W while the summed wire bytes match the replicated
        path's."""
        if len(state) != bk.num_buckets:
            raise ValueError(
                f"sharded state has {len(state)} buckets but the layout "
                f"needs {bk.num_buckets} — state was initialized from a "
                "different tree (init_shard_state and allreduce_shards "
                "must see the same pytree structure)")
        out_shards, new_states = [], []
        for i, (b, s) in enumerate(zip(shards, state)):
            with profile_scope(
                    f"{axis_name}_allreduce/bucket{i}_shard",
                    category="comm",
                    args={"bucket": i, "shard_elems": int(b.size),
                          "payload_bytes": self.inner.wire_bytes_leaf(
                              _bucket_leaf(int(b.size)))}):
                ob, ns = self.inner.allreduce_leaf(b, s, axis_name,
                                                   axis_size)
            out_shards.append(ob)
            new_states.append(ns)
        return out_shards, new_states

    def shard_wire_bytes(self, grads: Any, num_shards: int) -> int:
        """Per-chip dc-tier wire bytes on the ZeRO path: the inner
        compressor's payload for each 1/W bucket shard."""
        leaves = jax.tree.leaves(grads)
        if not leaves:
            return 0
        bk = self._bucketer(leaves)
        return sum(self.inner.wire_bytes_leaf(_bucket_leaf(n // num_shards))
                   for n in bk.bucket_sizes)

    def allreduce_leaf(self, g: jax.Array, state: Any, axis_name: str,
                       axis_size: int) -> Tuple[jax.Array, Any]:
        bk = self._bucketer([g])
        with profile_scope("compress/flatten"):
            bucket = bk.flatten([g])[0]
        out, new_state = self.inner.allreduce_leaf(bucket, state, axis_name,
                                                   axis_size)
        with profile_scope("compress/unflatten"):
            return bk.unflatten([out])[0], new_state

    # -- accounting ----------------------------------------------------------
    def wire_bytes(self, grads: Any) -> int:
        leaves = jax.tree.leaves(grads)
        if not leaves:
            return 0
        bk = self._bucketer(leaves)
        return sum(self.inner.wire_bytes_leaf(_bucket_leaf(n))
                   for n in bk.bucket_sizes)

    def wire_bytes_leaf(self, leaf: jax.Array) -> int:
        bk = self._bucketer([leaf])
        return self.inner.wire_bytes_leaf(_bucket_leaf(bk.bucket_sizes[0]))

    def layout_summary(self) -> Optional[dict]:
        """Static summary of the largest cached bucket layout (the
        gradient tree's), for the telemetry plane's host-side gauges
        (geomx_bucket_*): bucket count and the lane-padding waste the
        wire actually carries.  None before the first trace resolved a
        layout."""
        if not self._bucketers:
            return None
        bk = max(self._bucketers.values(),
                 key=lambda b: sum(b.bucket_fill) if b.bucket_fill else 0)
        fill = float(sum(bk.bucket_fill))
        padded = float(sum(bk.bucket_sizes))
        return {"num_buckets": bk.num_buckets,
                "bucket_elems": fill, "padded_elems": padded,
                "pad_fraction": (padded - fill) / padded if padded else 0.0}

    def bucket_report(self, grads: Any) -> List[dict]:
        """Per-bucket payload table (what the profiler spans report):
        true/padded elements, member-leaf
        count, and the inner compressor's wire bytes for the bucket."""
        leaves = jax.tree.leaves(grads)
        bk = self._bucketer(leaves)
        members = [0] * bk.num_buckets
        for b, _ in bk.assignments:
            members[b] += 1
        return [{"bucket": i, "elems": bk.bucket_fill[i],
                 "padded": bk.bucket_sizes[i], "leaves": members[i],
                 "wire_bytes": self.inner.wire_bytes_leaf(
                     _bucket_leaf(bk.bucket_sizes[i]))}
                for i in range(bk.num_buckets)]


def maybe_bucketed(comp: Compressor,
                   bucket_bytes: Optional[int] = None) -> Compressor:
    """The dc-tier default policy: wrap ``comp`` in a BucketedCompressor
    unless bucketing is disabled (``bucket_bytes=0`` /
    ``GEOMX_BUCKET_BYTES=0``) or ``comp`` already fuses the tree itself
    (BucketedCompressor, tree-level DGT)."""
    resolved = _resolve_bucket_bytes(bucket_bytes)
    if resolved <= 0 or getattr(comp, "fuses_tree", False):
        return comp
    return BucketedCompressor(comp, resolved)
