"""DGT — Differential Gradient Transmission, TPU-native re-expression.

Reference semantics (kv_app.h:1088-1196, van.cc:723-846): the push to the
global tier is sliced into fixed-size blocks; each block's *contribution*
is an EWMA of its mean |gradient|
(``contri = alpha*contri + (1-alpha)*mean|block|``, Evaluate_msg_contri,
kv_app.h:1047-1068); blocks are ranked by contribution, the top
``round(k * nblocks)`` go over reliable TCP (channel 0), the rest over N
UDP channels with descending DSCP priority (Get_channel, kv_app.h:1071-1086)
— i.e. less-important gradient blocks may arrive late (or, rarely, not at
all) without stalling the step.

On TPU there is no lossy channel and no DSCP; the *performance* content of
DGT — only the important fraction of the gradient is on the critical path,
the rest is delivered off the critical path — maps to a deferred-aggregation
schedule:

- top-k-by-contribution blocks are all-reduced immediately (channel 0);
- the remaining blocks accumulate into a device-local ``pending`` buffer
  (the in-flight UDP payload) and are delivered when either (a) their block
  becomes important, or (b) a periodic drain every ``channels`` steps fires
  (modelling the lower-priority channels' longer delivery time).

No gradient mass is ever dropped — matching DGT-with-reliable-resend
(Resender, ps-lite src/resender.h) rather than its lossiest configuration,
which is the convergence-safe choice.

Composes as a Compressor so DGT stacks under any sync algorithm and over
any inner wire compressor, mirroring ENABLE_DGT being orthogonal to the
sync mode in the reference.

TPU cost model (round-5 rework): the tree-level ``allreduce`` flattens
the WHOLE gradient pytree into one contiguous fp32 vector and runs the
deferral schedule once — one contribution EWMA, one top-k, one pending
read-modify-write, one inner all-reduce — instead of per-leaf.  Per-leaf
DGT on a ~25-leaf model meant ~25 tiny sorts + 100 extra state buffers
threaded through every dispatch (its cost on the chip, with HFA's dead
milestone carriage: not measured by the benchmark).  Ranking is therefore
GLOBAL across the model's blocks
rather than per-tensor; the reference ranks within each pushed key
(kv_app.h:1088-1196), but its k is the same fraction everywhere, so the
amortized wire volume is identical and global ordering is strictly
better at picking the important mass.  ``allreduce_leaf`` keeps the
exact per-leaf schedule for single-tensor callers and tests.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from geomx_tpu.compression.base import Compressor, NoCompressor


class DGTCompressor(Compressor):
    name = "dgt"
    # the tree-level allreduce below already fuses the whole gradient into
    # one flat buffer — the bucketing default must not wrap it again
    fuses_tree = True

    def __init__(self, inner: Optional[Compressor] = None,
                 block_elems: int = 1024, k: float = 0.5, alpha: float = 0.3,
                 channels: int = 1, k_min: float = 0.2, adaptive: bool = False):
        # defaults mirror kv_app.h:1036-1045 (DGT_BLOCK_SIZE=4096 bytes,
        # DMLC_K=0.5, DMLC_K_MIN=0.2, DGT_CONTRI_ALPHA=0.3,
        # DMLC_UDP_CHANNEL_NUM=1).  k_min/adaptive are accepted for config
        # parity: the reference parses ADAPTIVE_K_FLAG/DMLC_K_MIN
        # (kv_app.h:1041-1042) but never acts on them — dmlc_k is reset to
        # dmlc_k_init before every send (kv_app.h:1118,1228,1341) — so
        # matching behavior is a fixed k.
        self.inner = inner or NoCompressor()
        self.block_elems = max(1, int(block_elems))
        self.k = float(k)
        self.k_min = float(k_min)
        self.alpha = float(alpha)
        self.flush_every = max(1, int(channels))
        self.adaptive = adaptive

    def _nblocks(self, n: int) -> int:
        return -(-n // self.block_elems)

    def init_leaf_state(self, leaf: jax.Array) -> Any:
        nb = self._nblocks(leaf.size)
        return {
            "contri": jnp.zeros((nb,), jnp.float32),
            "pending": jnp.zeros((nb * self.block_elems,), jnp.float32),
            "step": jnp.zeros((), jnp.int32),
            "inner": self.inner.init_leaf_state(leaf),
        }

    def _defer_schedule(self, gf: jax.Array, state: Any):
        """The DGT core on one flat fp32 vector padded to whole blocks:
        returns (sendable flat vector, new state sans 'inner')."""
        nb = gf.shape[0] // self.block_elems
        blocks = (gf + state["pending"]).reshape(nb, self.block_elems)

        # contribution EWMA over mean |g| per block (kv_app.h:1058-1066)
        mag = jnp.mean(jnp.abs(gf.reshape(nb, self.block_elems)), axis=1)
        contri = self.alpha * state["contri"] + (1.0 - self.alpha) * mag

        # channel 0 = top round(k * nblocks) blocks (Get_channel min_index)
        k_now = max(1, int(round(self.k * nb)))
        if k_now >= nb:
            send_mask = jnp.ones((nb,), bool)
        else:
            kth = lax.top_k(contri, k_now)[0][-1]
            send_mask = contri >= kth
        # periodic drain of the deferred channels
        step = state["step"]
        drain = (step + 1) % self.flush_every == 0
        send_mask = jnp.logical_or(send_mask, drain)

        sendable = jnp.where(send_mask[:, None], blocks, 0.0).reshape(-1)
        pending = jnp.where(send_mask[:, None], 0.0, blocks).reshape(-1)
        return sendable, {"contri": contri, "pending": pending,
                          "step": step + 1}

    def allreduce_leaf(self, g: jax.Array, state: Any, axis_name: str,
                       axis_size: int) -> Tuple[jax.Array, Any]:
        shape, dtype, n = g.shape, g.dtype, g.size
        padded = self._nblocks(n) * self.block_elems
        gf = jnp.zeros((padded,), jnp.float32).at[:n].set(
            g.reshape(-1).astype(jnp.float32))
        sendable, new_state = self._defer_schedule(gf, state)
        summed, inner_state = self.inner.allreduce_leaf(
            sendable[:n].reshape(shape).astype(dtype),
            state["inner"], axis_name, axis_size)
        new_state["inner"] = inner_state
        return summed, new_state

    # -- tree-level fast path (see module docstring: one schedule for the
    # -- whole gradient instead of one per leaf) ---------------------------
    def init_state(self, grads: Any) -> Any:
        n = sum(leaf.size for leaf in jax.tree.leaves(grads))
        padded = self._nblocks(n) * self.block_elems
        flat = jnp.zeros((padded,), jnp.float32)
        return {
            "contri": jnp.zeros((self._nblocks(n),), jnp.float32),
            "pending": flat,
            "step": jnp.zeros((), jnp.int32),
            "inner": self.inner.init_leaf_state(flat),
        }

    def allreduce(self, grads: Any, state: Any, axis_name: str,
                  axis_size: int) -> Tuple[Any, Any]:
        leaves, treedef = jax.tree.flatten(grads)
        n = sum(leaf.size for leaf in leaves)
        padded = self._nblocks(n) * self.block_elems
        flat = jnp.concatenate(
            [leaf.reshape(-1).astype(jnp.float32) for leaf in leaves])
        gf = jnp.zeros((padded,), jnp.float32).at[:n].set(flat)
        sendable, new_state = self._defer_schedule(gf, state)
        # the inner compressor sees ONE flat vector — its error-feedback /
        # velocity state lives on the same flat layout (init_state above)
        summed, inner_state = self.inner.allreduce_leaf(
            sendable, state["inner"], axis_name, axis_size)
        new_state["inner"] = inner_state
        out, off = [], 0
        for leaf in leaves:
            out.append(summed[off:off + leaf.size].reshape(leaf.shape)
                       .astype(leaf.dtype))
            off += leaf.size
        return treedef.unflatten(out), new_state

    def wire_bytes_leaf(self, leaf: jax.Array) -> int:
        """Amortized bytes per sync.  Non-drain steps move ~k of the
        blocks, but every ``flush_every``-th step is a drain that sends
        everything pending, so the honest steady-state average is

            (flush_every - 1) * k + 1   of   flush_every   full payloads

        (k for the top blocks each step, the full tensor on the drain)."""
        inner_bytes = self.inner.wire_bytes_leaf(leaf)
        f = self.flush_every
        frac = (min(1.0, self.k) * (f - 1) + 1.0) / f
        return int(inner_bytes * frac)
