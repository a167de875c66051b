"""The reference trainer: what `Trainer.fit` computes in its first steps,
in plain jax.numpy on one device.

Per step, every (party, worker) slot takes the loss and gradient of its
own rows; a party's gradient is the mean over its workers; the dc tier is
a dense mean over parties, or the Bi-Sparse push of `bisparse.py` with
its per-party error feedback; one plain Adam applies the result.  Returns
the numbers the comparison reads: each step's mean loss, the first
gradient as the dc tier gets it (the mean over parties, before any
compression; a list of leaves on the device), and the per-leaf norm of the
parameters' change after the last step.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.references import bisparse


def leaf_norms(tree) -> np.ndarray:
    fn = jax.jit(lambda t: jnp.stack(
        [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
         for x in jax.tree.leaves(t)]))
    return np.asarray(fn(tree), np.float64)


@jax.jit
def _adam(params, m, v, g, t, lr, b1, b2, eps):
    m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
    v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    params = jax.tree.map(
        lambda p, m_, v_: p - lr * (m_ / c1) / (jnp.sqrt(v_ / c2) + eps),
        params, m, v)
    return params, m, v


class BiSparseTier:
    """Per-party error feedback on the bucket layout."""

    def __init__(self, params, parties: int, ratio: float,
                 bucket_bytes: int):
        leaves = jax.tree.leaves(params)
        self.ratio = float(ratio)
        self.layout = bisparse.bucket_layout(
            [int(x.size) for x in leaves], bucket_bytes)
        self.state = [[(jnp.zeros((n,), jnp.float32),
                        jnp.zeros((n,), jnp.float32))
                       for _, _, n in self.layout] for _ in range(parties)]

    def combine(self, party_grads):
        treedef = jax.tree.structure(party_grads[0])
        leaves = [jax.tree.leaves(g) for g in party_grads]
        out = []
        for b, (lo, hi, n) in enumerate(self.layout):
            parts = []
            for p, party in enumerate(leaves):
                u, v = self.state[p][b]
                sent, u, v = bisparse.push_leaves(party[lo:hi], u, v, n=n,
                                                  ratio=self.ratio)
                self.state[p][b] = (u, v)
                parts.append(sent)
            out.extend(bisparse.split_bucket(
                parts, tuple(tuple(x.shape) for x in leaves[0][lo:hi])))
        return treedef.unflatten(out)


def dense_mean(party_grads):
    if len(party_grads) == 1:
        return party_grads[0]
    return jax.tree.map(lambda *g: sum(g) / len(g), *party_grads)


def reference_steps(loss_fn, params, batches, optimizer: dict,
                    compression: str, bucket_bytes: int):
    """`loss_fn(params, x, y)` scalar; `batches` a list of (x, y) host
    arrays [P, W, b, ...], one per step; `optimizer` the configuration's
    {"name": "adam", "lr", "b1", "b2", "eps"}.  See the module's head."""
    if optimizer["name"] != "adam":
        raise ValueError(f"no plain form of {optimizer['name']!r}")
    grad_fn = jax.jit(jax.value_and_grad(loss_fn))
    parties, workers = batches[0][0].shape[:2]
    tier = None
    kind, _, arg = compression.partition(",")
    if kind == "bsc":
        tier = BiSparseTier(params, parties, float(arg), bucket_bytes)
    elif kind != "none":
        raise ValueError(f"no plain form of compression {compression!r}")
    start = params
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, first = [], None
    for t, (x, y) in enumerate(batches, start=1):
        party_grads, step_losses = [], []
        for p in range(parties):
            acc = None
            for w in range(workers):
                value, g = grad_fn(params, x[p, w], y[p, w])
                step_losses.append(value)
                acc = g if acc is None else jax.tree.map(jnp.add, acc, g)
            if workers > 1:
                acc = jax.tree.map(lambda a: a / workers, acc)
            party_grads.append(acc)
        if first is None:
            # the gradient as the dc tier gets it: see run.first_gradient
            first = jax.tree.leaves(dense_mean(party_grads))
        g = tier.combine(party_grads) if tier else dense_mean(party_grads)
        del party_grads, acc
        params, m, v = _adam(params, m, v, g, float(t), optimizer["lr"],
                             optimizer["b1"], optimizer["b2"],
                             optimizer["eps"])
        losses.append(float(np.mean([float(s) for s in step_losses])))
    delta = leaf_norms(jax.tree.map(jnp.subtract, params, start))
    return {"losses": losses, "first_grad": first, "delta_norms": delta}
