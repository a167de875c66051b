"""Sampled-boundary top-k selection — the reference's actual BSC scan.

The reference's BSCompress does NOT run an exact top-k: it estimates the
magnitude boundary from a random sample of ~0.5% of the elements, then
scans once, zipping (value, index) pairs that clear the boundary into a
fixed ``k``-slot wire buffer, padding the tail with sentinels
(src/kvstore/gradient_compression.cc:219-259).  That algorithm is
O(n) with one ordered pass — and it is MUCH more TPU-friendly than a
real top-k: threshold from a tiny sorted sample, then a fused
mask+cumsum+scatter over the tensor.  No O(n log n) sort, no
approx_max_k reduction tree.

Fixed-size semantics match the reference exactly:
- exactly ``k`` output slots;
- if more than ``k`` elements clear the boundary, the FIRST ``k`` in
  index order win (the reference's scan stops filling when the buffer
  is full);
- if fewer clear it, the tail is sentinel (-1) indices that decompress
  drops; the unsent mass stays in the error-feedback buffers.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def sample_positions(n: int, sample: int = 8192) -> np.ndarray:
    """Deterministic quasi-random sample positions (Weyl/multiplicative
    sequence): a plain stride slice would systematically miss magnitude
    structure correlated with position mod stride; this decorrelates
    from any fixed layout while staying deterministic (the reference
    seeds its random sampler the same way every run)."""
    m = min(n, int(sample))
    return (np.arange(m, dtype=np.int64) * 2654435761) % n


def boundary_position(m: int, k, n: int):
    """Index into the sorted ``m``-element probe for the (1 - k/n)
    quantile.  A Python-int ``k`` resolves statically (what
    GEOMX_CONTROL=0 traces); a TRACED ``k`` (the Graft
    Pilot's no-recompile ratio operand, control/actuators.py) returns a
    traced position the gather below consumes without a shape change."""
    if isinstance(k, (int, np.integer)):
        return min(max(int(round(m * (1.0 - int(k) / n))), 0), m - 1)
    pos = jnp.round(m * (1.0 - k.astype(jnp.float32) / n))
    return jnp.clip(pos, 0, m - 1).astype(jnp.int32)


def sampled_threshold_select(v: jax.Array, absv: jax.Array, k: int, thr):
    """Select ~top-k of ``absv`` against the magnitude boundary ``thr``
    (``bsc_pallas.sampled_boundary_guv``).

    Returns (vals[k], idx[k] int32 with -1 sentinels, keep[n] bool —
    the dense mask of emitted coordinates, for error-feedback resets).
    """
    n = absv.shape[0]
    k = int(k)
    # two-tier selection: strictly-above-boundary elements claim slots
    # FIRST, boundary-tied elements fill whatever remains.  A plain
    # inclusive mask starves real mass on sparse gradients (thr == 0 ->
    # the first k zeros win by index order); a plain strict mask starves
    # constant-magnitude gradients (everything tied at thr -> nothing
    # ever emitted, and uniform error feedback keeps the tie forever).
    primary = absv > thr
    secondary = absv == thr
    p_i = primary.astype(jnp.int32)
    s_i = secondary.astype(jnp.int32)
    p_rank = jnp.cumsum(p_i) - p_i              # exclusive rank among >
    n_primary = jnp.sum(p_i)
    s_rank = n_primary + jnp.cumsum(s_i) - s_i  # ties queue after all >
    rank = jnp.where(primary, p_rank, s_rank)
    mask = primary | secondary
    keep = mask & (rank < k)
    # scatter kept coordinates into their rank slot; overflow and
    # non-hits pile into the dump slot k (dropped)
    slot = jnp.where(keep, rank, k)
    idx_full = jnp.full((k + 1,), -1, jnp.int32).at[slot].set(
        jnp.arange(n, dtype=jnp.int32))
    idx = idx_full[:k]
    valid = idx >= 0
    vals = jnp.where(valid, v[jnp.where(valid, idx, 0)], 0.0)
    return vals, idx, keep
