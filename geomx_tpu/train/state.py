"""Replica-local training state.

Design note (the central TPU-native choice of this framework): the
reference keeps parameters in per-process NDArrays — every worker, local
server and global server holds its own copy, and divergence between copies
is exactly what the sync algorithms manage (HFA lets workers drift for K1
steps; MixedSync serves stale weights).  The SPMD equivalent is
*device-local state with explicit replica axes*: every state leaf carries
leading axes ``[num_parties, workers_per_party]`` sharded
``P("dc", "worker")``, so each device owns precisely its own copy — same
total memory as XLA replication, but drift becomes expressible.  Sync
algorithms are then collectives that re-align slices of those axes.

Under FSA all copies stay bit-identical (the hierarchical all-reduce and
the deterministic optimizer guarantee it); ``unreplicate_tree`` takes copy
(0, 0) for eval/checkpoint, matching the reference reading weights from
rank 0.
"""

from __future__ import annotations

from typing import Any

import jax
import numpy as np
from flax import struct
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from geomx_tpu.topology import DC_AXIS, WORKER_AXIS, HiPSTopology


class TrainState(struct.PyTreeNode):
    step: jax.Array          # scalar, replicated
    params: Any              # leaves [P, W, ...] sharded P(dc, worker)
    opt_state: Any
    model_state: Any         # non-trainable collections (BatchNorm stats)
    sync_state: Any          # sync-algorithm state (milestones, residuals, ...)


def state_specs() -> TrainState:
    """PartitionSpec prefix-tree matching TrainState for shard_map."""
    rep = P(DC_AXIS, WORKER_AXIS)
    return TrainState(step=P(), params=rep, opt_state=rep,
                      model_state=rep, sync_state=rep)


def replicate_tree(tree: Any, topology: HiPSTopology, mesh: Mesh) -> Any:
    """Broadcast every leaf to [P, W, *shape] with P(dc, worker) sharding.

    The broadcast is a zero-copy numpy view; device_put materializes one
    copy per device — identical footprint to plain replication.
    """
    sharding = NamedSharding(mesh, P(DC_AXIS, WORKER_AXIS))
    shape2 = (topology.num_parties, topology.workers_per_party)

    def rep(x):
        x = np.asarray(x)
        return jax.device_put(np.broadcast_to(x[None, None], shape2 + x.shape),
                              sharding)

    return jax.tree.map(rep, tree)


def replicate_consuming(trees: list, topology: HiPSTopology,
                        mesh: Mesh) -> list:
    """`replicate_tree` over a list of trees that the caller gives up: the
    list is emptied, and each leaf is let go as soon as its replicated
    copy exists, so that the device never holds the whole state twice
    (a 600 M-parameter model's weights and Adam moments are 7.2 GB)."""
    out = []
    while trees:
        leaves, treedef = jax.tree.flatten(trees.pop(0))
        done = []
        while leaves:
            done.append(replicate_tree(leaves.pop(0), topology, mesh))
        out.append(treedef.unflatten(done))
    return out


def unreplicate_tree(tree: Any) -> Any:
    """Copy (party 0, worker 0) of every leaf, for eval/checkpoint."""
    return jax.tree.map(lambda x: np.asarray(jax.device_get(x))[0, 0], tree)
