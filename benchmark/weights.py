"""Weights from the seed, made by the benchmark and handed to both sides:
the program's state and the plain reference start from the same numbers,
and the reference takes nothing the program has made.

One jitted call fills every leaf on the device: leaf i of the tree (in
flatten order) draws from fold_in(key(seed), i); a leaf named `scale` is
ones, every other leaf is normal times the family's `weight_std(path,
shape)`.  `lead` prepends the program's replica axes."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A key from any whole number up to 2**62 (PRNGKey alone takes 32
    signed bits where x64 is off)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % (1 << 31)),
                              seed >> 31)


def make_weights(family, shapes, seed: int, lead=(), out_shardings=None):
    """`shapes`: a tree of objects with `.shape` (without `lead`)."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    specs = []
    for path, leaf in paths:
        names = tuple(getattr(k, "key", getattr(k, "name", str(k)))
                      for k in path)
        shape = tuple(leaf.shape)
        specs.append((shape, None if names[-1] == "scale"
                      else float(family.weight_std(names, shape))))

    def fill(key):
        out = []
        for i, (shape, std) in enumerate(specs):
            if std is None:
                x = jnp.ones(shape, jnp.float32)
            else:
                x = std * jax.random.normal(jax.random.fold_in(key, i),
                                            shape, jnp.float32)
            out.append(jnp.broadcast_to(x, tuple(lead) + shape))
        return treedef.unflatten(out)

    return jax.jit(fill, out_shardings=out_shardings)(seed_key(seed))
