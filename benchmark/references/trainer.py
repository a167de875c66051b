"""The reference trainer: what `Trainer.fit` computes in its first steps,
in plain jax.numpy on one device.

Per step, every (party, worker) slot takes the loss and gradient of its
own rows; a party's gradient is the mean over its workers; the dc tier is
a dense mean over parties, or the Bi-Sparse push of `bisparse.py` with
its per-party error feedback; one plain Adam applies the result.  Returns
the numbers the comparison reads: each step's mean loss, the first
gradient as the dc tier gets it (the mean over parties, before any
compression; a list of leaves on the host), and the per-leaf norm of the
parameters' change after the last step.

What it holds on the device, in bytes of the parameters P, so that the
check fits wherever the program's own 16 bytes a parameter did: between
steps 3 P (weights and Adam's two moments) and the tier's state (none
for the dense mean, 2 P a party for Bi-Sparse); at a gradient call one
gradient and the loss function's activations more; at the Adam call 4 P,
because Adam writes into its inputs' buffers.  What is read only at the
end waits elsewhere: the first gradient on the host, the starting weights
in the seed (`make_params` is called a second time once Adam's state is
gone).  With several parties one party's gradient is alive at a time, and
the running sum of the dense mean or the pushes' payloads beside it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.references import bisparse


def leaf_norms(tree) -> np.ndarray:
    fn = jax.jit(lambda t: jnp.stack(
        [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
         for x in jax.tree.leaves(t)]))
    return np.asarray(fn(tree), np.float64)


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _adam(params, m, v, g, t, lr, b1, b2, eps):
    """Gives params, m and v back in their inputs' buffers.  The gradient's
    has no output to take it (donating it too only earns XLA's warning that
    it was not usable): the caller drops it."""
    m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
    v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    params = jax.tree.map(
        lambda p, m_, v_: p - lr * (m_ / c1) / (jnp.sqrt(v_ / c2) + eps),
        params, m, v)
    return params, m, v


class BiSparseTier:
    """Per-party error feedback on the bucket layout.  A party is pushed
    as soon as its gradient exists (`push`); `merged` then gives the mean
    of the parties' payloads, cut back into the gradient's leaves."""

    def __init__(self, params, parties: int, ratio: float,
                 bucket_bytes: int):
        leaves = jax.tree.leaves(params)
        self.treedef = jax.tree.structure(params)
        self.shapes = tuple(tuple(x.shape) for x in leaves)
        self.ratio = float(ratio)
        self.layout = bisparse.bucket_layout(
            [int(x.size) for x in leaves], bucket_bytes)
        self.state = [[(jnp.zeros((n,), jnp.float32),
                        jnp.zeros((n,), jnp.float32))
                       for _, _, n in self.layout] for _ in range(parties)]
        self.sent = [[] for _ in self.layout]

    def push(self, party: int, grads) -> None:
        leaves = jax.tree.leaves(grads)
        for b, (lo, hi, n) in enumerate(self.layout):
            u, v = self.state[party][b]
            sent, u, v = bisparse.push_leaves(leaves[lo:hi], u, v, n=n,
                                              ratio=self.ratio)
            self.state[party][b] = (u, v)
            self.sent[b].append(sent)

    def merged(self):
        out = []
        for b, (lo, hi, _n) in enumerate(self.layout):
            parts, self.sent[b] = self.sent[b], []
            out.extend(bisparse.split_bucket(parts, self.shapes[lo:hi]))
        return self.treedef.unflatten(out)


def add_party(total, grads):
    """Python's `sum` over the parties' gradients, a party at a time:
    0 + g0, then + g1, and so on, leaf by leaf."""
    if total is None:
        return jax.tree.map(lambda g: 0 + g, grads)
    return jax.tree.map(jnp.add, total, grads)


def reference_steps(loss_fn, make_params, batches, optimizer: dict,
                    compression: str, bucket_bytes: int):
    """`loss_fn(params, x, y)` scalar; `make_params()` the starting weights
    on the device, the same at every call; `batches` a list of (x, y) host
    arrays [P, W, b, ...], one per step; `optimizer` the configuration's
    {"name": "adam", "lr", "b1", "b2", "eps"}.  See the module's head."""
    if optimizer["name"] != "adam":
        raise ValueError(f"no plain form of {optimizer['name']!r}")
    grad_fn = jax.jit(jax.value_and_grad(loss_fn))
    parties, workers = batches[0][0].shape[:2]
    params = make_params()
    tier = None
    kind, _, arg = compression.partition(",")
    if kind == "bsc":
        tier = BiSparseTier(params, parties, float(arg), bucket_bytes)
    elif kind != "none":
        raise ValueError(f"no plain form of compression {compression!r}")
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, first = [], None
    for t, (x, y) in enumerate(batches, start=1):
        step_losses, g = [], None
        for p in range(parties):
            acc = None
            for w in range(workers):
                value, grad = grad_fn(params, x[p, w], y[p, w])
                step_losses.append(value)
                acc = grad if acc is None else jax.tree.map(jnp.add, acc, grad)
                del grad
            if workers > 1:
                acc = jax.tree.map(lambda a: a / workers, acc)
            if tier is None or first is None:
                g = acc if parties == 1 else add_party(g, acc)
            if tier:
                tier.push(p, acc)
            del acc
        if g is not None and parties > 1:
            g = jax.tree.map(lambda s: s / parties, g)
        if first is None:
            # the gradient as the dc tier gets it (see run.first_gradient),
            # read only at the end: it waits on the host
            first = jax.device_get(jax.tree.leaves(g))
        if tier:
            g = None            # the dense mean goes before the payloads merge
            g = tier.merged()
        params, m, v = _adam(params, m, v, g, float(t), optimizer["lr"],
                             optimizer["b1"], optimizer["b2"],
                             optimizer["eps"])
        del g
        losses.append(float(np.mean([float(s) for s in step_losses])))
    del m, v, tier
    delta = leaf_norms(jax.tree.map(jnp.subtract, params, make_params()))
    return {"losses": losses, "first_grad": first, "delta_norms": delta}
