"""The Mamba-2 state-space recurrence (SSD), as a chunkwise-parallel
algorithm.

Per head, with a state ``S`` in R^(P x N), a scalar decay a head and
token, and ``B``, ``C`` shared by the heads of a group::

    S_t = exp(a dt_t) S_(t-1) + dt_t x_t B_t^T          (a < 0, dt_t > 0)
    y_t = S_t C_t

A token-by-token scan of this is 8,192 dependent steps a sequence; here a
chunk of ``chunk`` tokens is folded into matrix products (Dao and Gu's
"state-space duality"): within a chunk ``y = (C B^T * L * dt) x`` with
``L[i, j] = exp(G_i - G_j)`` for ``j <= i`` (``G`` the inclusive
cumulative log-decay within the chunk); a chunk's own contribution to the
state is one product of ``B`` against the tokens decayed to the chunk's
end; the states that enter the chunks are one product of those
contributions against the chunk-to-chunk decays (a lower-triangular
matrix over the chunks, so no loop at all); and what the entering state
adds to a token is one more product, decayed from the chunk's start.

Decay lives in float32 log space and only differences ``G_i - G_j <= 0``
are ever exponentiated.  The chunk-to-chunk hand-over is float32 at
HIGHEST (64 x 64 a head at 8,192 tokens: nothing beside the rest); the
large products take operands of ``dtype`` and accumulate in float32.

The backward pass is JAX's own; callers rematerialise (`jax.checkpoint`)
the layer that holds the call.  This is the jnp form and, today, the only
one: `ops/dispatch.ssd` is the door a kernel would come in by.
`tests/test_ssd.py` holds it to the token recurrence of
`benchmark/references/nemotron_h.py`, values and gradients.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from geomx_tpu.utils.profiler import profile_scope

_HIGHEST = lax.Precision.HIGHEST


def ssd_chunked(x, dt, a, b, c, chunk: int = 128, dtype=jnp.float32):
    """x [B, L, H, P]; dt [B, L, H] (the step, > 0); a [H] (< 0); b, c
    [B, L, G, N] with head h in group h // (H / G); the state starts at
    zero.  Returns y [B, L, H, P] float32.  ``dtype``: the operands of the
    large matrix products (they accumulate in float32; decay, cumulative
    sums and the chunk-to-chunk hand-over are float32 throughout).  Any L:
    the tail is padded with tokens that neither write nor decay (dt 0)."""
    bsz, length, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    if heads % groups:
        raise ValueError(f"{heads} heads are not whole groups of {groups}")
    per = heads // groups
    pad = (-length) % chunk
    if pad:
        widen = lambda y: jnp.pad(
            y, ((0, 0), (0, pad)) + ((0, 0),) * (y.ndim - 2))
        x, dt, b, c = map(widen, (x, dt, b, c))
    nc = (length + pad) // chunk
    f32 = jnp.float32
    dot = lambda spec, u, v: jnp.einsum(spec, u, v,
                                        preferred_element_type=f32)

    with profile_scope("ssd/scan", "kernel"):
        # [B, nc, Q, ...]: cutting time into chunks moves no data
        xc = x.reshape(bsz, nc, chunk, groups, per, p).astype(dtype)
        bc = b.reshape(bsz, nc, chunk, groups, n).astype(dtype)
        cc = c.reshape(bsz, nc, chunk, groups, n).astype(dtype)
        # heads-major [B, nc, G, R, Q] for everything a head owns
        dtc = jnp.moveaxis(dt.astype(f32).reshape(
            bsz, nc, chunk, groups, per), 2, -1)
        g_cum = jnp.cumsum(dtc * a.astype(f32).reshape(groups, per, 1), -1)

        # inside a chunk: token j as token i (j <= i) reads it
        row = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        col = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
        decay = jnp.exp(jnp.where(
            col <= row, g_cum[..., :, None] - g_cum[..., None, :], -jnp.inf))
        scores = dot("bcign,bcjgn->bcgij", cc, bc)           # C_i . B_j
        mix = (scores[:, :, :, None] * decay
               * dtc[..., None, :]).astype(dtype)            # [.., G, R, Q, Q]
        y = dot("bcgrij,bcjgrp->bcigrp", mix, xc)

        # a chunk's own part of the state at its end, [B, nc, G, R, P, N]
        to_end = jnp.exp(g_cum[..., -1:] - g_cum) * dtc      # [.., G, R, Q]
        written = dot("bcjgn,bcjgrp->bcgrpn", bc,
                      (xc * jnp.moveaxis(to_end, -1, 2)[..., None])
                      .astype(dtype))
        # the state that enters chunk k: sum over m < k of the parts,
        # decayed over the chunks between
        total = g_cum[..., -1]                               # [B, nc, G, R]
        through = jnp.cumsum(total, axis=1)
        k = lax.broadcasted_iota(jnp.int32, (nc, nc), 0)
        m = lax.broadcasted_iota(jnp.int32, (nc, nc), 1)
        before = through - total                             # up to k's start
        hand = jnp.exp(jnp.where(
            (m < k)[:, :, None, None],
            before[:, :, None] - through[:, None, :], -jnp.inf))
        entering = jnp.einsum("bkmgr,bmgrpn->bkgrpn", hand, written,
                              precision=_HIGHEST)
        # what the entering state adds to token i, decayed from the start
        carried = dot("bcign,bcgrpn->bcigrp", cc, entering.astype(dtype))
        y = y + carried * jnp.moveaxis(jnp.exp(g_cum), -1, 2)[..., None]
    return y.reshape(bsz, nc * chunk, heads, p)[:, :length]

