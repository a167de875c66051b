"""Plain form of the Bi-Sparse push the program runs on a TPU, written
from its description (gradient_compression.cc BSCompress and the repo's
docs), importing nothing of `compression/` or `ops/`.

Per flat bucket of n float32 elements, with k = max(1, ceil(ratio * n)):

1. momentum correction with error feedback: u = 0.9 u + g; v = v + u;
2. the boundary: |v| at min(n, 8192) fixed probe positions
   (i * 2654435761 mod n), sorted; the boundary is the element at position
   round(m * (1 - k / n)), clipped to [0, m - 1];
3. elements strictly above the boundary claim the k slots first, in index
   order; elements equal to it fill what remains, in index order;
4. emitted coordinates are zeroed in u and v; what was not sent stays.

The dc tier sums every party's emitted pairs into a dense vector and
divides by the number of parties.  Buckets: the gradient's leaves in
flatten order fill a bucket greedily up to `bucket_bytes` of float32; a
leaf never splits; each bucket is zero-padded to a multiple of 128.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

MOMENTUM = 0.9
PROBE = 8192
WEYL = 2654435761
MIN_SPARSE = 1024


def bucket_layout(leaf_sizes, bucket_bytes: int, pad_to: int = 128):
    """[(first_leaf, last_leaf_exclusive, padded_size)] per bucket."""
    capacity = max(pad_to, bucket_bytes // 4)
    buckets, start, fill = [], 0, 0
    for i, size in enumerate(leaf_sizes):
        if fill > 0 and fill + size > capacity:
            buckets.append((start, i, -(-fill // pad_to) * pad_to))
            start, fill = i, 0
        fill += size
    if leaf_sizes:
        buckets.append((start, len(leaf_sizes), -(-fill // pad_to) * pad_to))
    return buckets


def k_for(n: int, ratio: float) -> int:
    return max(1, int(math.ceil(n * ratio)))


def boundary_position(n: int, k: int) -> int:
    m = min(n, PROBE)
    return min(max(int(round(m * (1.0 - k / n))), 0), m - 1)


def probe_positions(n: int) -> np.ndarray:
    return ((np.arange(min(n, PROBE), dtype=np.int64) * WEYL) % n).astype(
        np.int32)


def boundary(absv, k: int):
    n = absv.shape[0]
    return jnp.sort(absv[probe_positions(n)])[boundary_position(n, k)]


def select(absv, thr, k: int):
    """The dense mask of emitted coordinates under the rule's step 3."""
    primary = absv > thr
    tie = absv == thr
    p_i = primary.astype(jnp.int32)
    t_i = tie.astype(jnp.int32)
    rank = jnp.where(primary, jnp.cumsum(p_i) - p_i,
                     jnp.sum(p_i) + jnp.cumsum(t_i) - t_i)
    return (primary | tie) & (rank < k)


@functools.partial(jax.jit, static_argnames=("n", "ratio"))
def push_leaves(leaves, u, v, n: int, ratio: float):
    """`push_bucket` of the bucket that `leaves` fill, zero-padded to n."""
    flat = jnp.concatenate([x.reshape(-1).astype(jnp.float32)
                            for x in leaves])
    return push_bucket(jnp.pad(flat, (0, n - flat.shape[0])), u, v,
                       ratio=ratio)


@functools.partial(jax.jit, static_argnames=("shapes",))
def split_bucket(parts, shapes):
    """The mean over parties of their emitted buckets, cut back into
    leaves of `shapes`."""
    total = sum(parts) / len(parts) if len(parts) > 1 else parts[0]
    out, off = [], 0
    for shape in shapes:
        size = int(np.prod(shape, dtype=np.int64))
        out.append(total[off:off + size].reshape(shape))
        off += size
    return out


@functools.partial(jax.jit, static_argnames=("ratio",))
def push_bucket(g, u, v, ratio: float):
    """One party's push of one bucket: (emitted dense, new u, new v)."""
    n = g.shape[0]
    if n < MIN_SPARSE:
        return g, u, v
    u = MOMENTUM * u + g
    v = v + u
    keep = select(jnp.abs(v), boundary(jnp.abs(v), k_for(n, ratio)),
                  k_for(n, ratio))
    return (jnp.where(keep, v, 0.0), jnp.where(keep, 0.0, u),
            jnp.where(keep, 0.0, v))


@functools.partial(jax.jit, static_argnames=("ratio",))
def payload_facts(emitted, residual, ratio: float):
    """What the rule guarantees of one bucket's first push, read from what
    the program's step left behind: `emitted` is the dense vector the
    optimizer received, `residual` the velocity buffer afterwards.  Their
    sum is the accumulated gradient the rule saw (first step: u = v = g).

    Returns counts, each 0 (or equal) where the program kept the rule:
    `overlap` coordinates both emitted and kept back; `below` emitted
    coordinates under the boundary; `held` coordinates above the boundary
    that were kept back although slots were free; `count` emitted, `k`,
    and `plain_count`, what this file's `select` emits on the same input.
    The boundary is compared with a slack of 2e-6: the emitted values are
    recovered from Adam's first moment, one rounding away from exact."""
    n = emitted.shape[0]
    k = k_for(n, ratio)
    sent = emitted != 0.0
    acc = emitted + residual
    absv = jnp.abs(acc)
    thr = boundary(absv, k)
    count = jnp.sum(sent)
    plain = jnp.sum(select(absv, thr, k))
    return {
        "overlap": jnp.sum(sent & (residual != 0.0)),
        "below": jnp.sum(sent & (absv < thr * (1 - 2e-6))),
        "held": jnp.where(count < k,
                          jnp.sum(~sent & (absv > thr * (1 + 2e-6))), 0),
        "count": count, "k": jnp.asarray(k), "plain_count": plain,
    }
