"""Time the held experts' layer on the chip, at the benchmark cell's size.

Makes ``T`` tokens of width ``d`` (bfloat16), ``E`` held experts of width
``f`` and, for each named load, a routing in which held expert ``e``
gets exactly the load's ``e``-th count of assignments (each on a token
of its own, the other choices on absent experts); times one jitted
program that runs `ops.held_experts.held_experts` forward and backward
(value and every gradient) and prints milliseconds per call, medians
over ``--reps`` runs after a warm-up:

- ``new``: the module as it stands; ``new@k,n/k,n``: the same with other
  caps on the kernels' tiles (`GMM_TILES` / `TGMM_TILES`);
- ``other``: ``held_experts`` of the module given with ``--other`` (a
  parent commit's file), with every gradient's distance from ``new``'s
  over its norm.

``--pieces`` times the first pool's gather and its scatter-add alone (a
host-timed call costs ~0.6 ms whatever it does).  A step of the cell calls
the layer four times (its four expert layers), each forward and backward
once: the rematerialised forward needs the sorted assignments again, not
the walk.  One JSON line per variant and load.

    python tools/held_experts_timing.py --other _scratch/held_experts_old.py
"""
import argparse
import importlib.util
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

LOADS = {
    "even": [512] * 8,
    "seeded": [4, 60, 200, 350, 500, 700, 900, 3406],
    "emptied": [1, 2, 0, 3, 1, 2, 1, 3],
    "none": [0] * 8,
    "doubled": [1003] * 8,
    "one_takes_all": [15557, 100, 30, 5, 0, 2, 1, 60],
}


def routing(rng, tokens, top_k, counts, absent):
    """idx [T, k]: expert e (column e) on counts[e] distinct tokens."""
    import numpy as np
    idx = np.full((tokens, top_k), absent, np.int32)
    for e, count in enumerate(counts):
        idx[rng.choice(tokens, size=min(count, tokens), replace=False), e] = e
    return idx


def median_ms(fn, args, reps):
    import jax
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(1e3 * (time.perf_counter() - t0))
    return statistics.median(times)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--tokens", type=int, default=16384)
    parser.add_argument("--hidden", type=int, default=2304)
    parser.add_argument("--width", type=int, default=1024)
    parser.add_argument("--rows", type=int, default=512)
    parser.add_argument("--reps", type=int, default=5)
    parser.add_argument("--other", default=None,
                        help="path of another held_experts.py to time too")
    parser.add_argument("--tiles", default="",
                        help="more caps to try, `k,n/k,n` (gmm/tgmm), "
                             "separated by spaces")
    parser.add_argument("--loads", default=",".join(LOADS))
    parser.add_argument("--pieces", action="store_true",
                        help="time a pool's gather and scatter-add alone")
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from geomx_tpu.ops import held_experts as ours

    rng = np.random.default_rng(0)
    top_k, held = 8, 8
    t, d, f = args.tokens, args.hidden, args.width
    x = jnp.asarray(rng.standard_normal((t, d)), jnp.bfloat16)
    w = jnp.asarray(rng.uniform(0.1, 0.5, (t, top_k)), jnp.float32)
    r = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
    mats = [jnp.asarray(rng.standard_normal(s) * 0.02, jnp.float32)
            for s in ((held, d, f), (held, d, f), (held, f, d))]
    idxs = {name: jnp.asarray(routing(rng, t, top_k, LOADS[name], 200))
            for name in args.loads.split(",")}

    def program(fn):
        def loss(x_, w_, gate, up, down, idx):
            y, counts, dropped = fn(x_, idx, w_, gate, up, down, 0, args.rows)
            return jnp.sum(y * r), (counts, dropped)
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4),
                                          has_aux=True))

    variants = [("new", None)]
    for caps in args.tiles.split():
        variants.append((f"new@{caps}", caps))
    reference = {}
    for name, caps in variants:
        if caps:
            g, tg = caps.split("/")
            ours.GMM_TILES = tuple(int(v) for v in g.split(","))
            ours.TGMM_TILES = tuple(int(v) for v in tg.split(","))
        run = program(ours.held_experts)
        for load, idx in idxs.items():
            (_, (counts, dropped)), grads = run(x, w, *mats, idx)
            assert int(dropped) == 0 and [int(c) for c in counts] == [
                min(c, t) for c in LOADS[load]], (load, counts, dropped)
            if name == "new":
                reference[load] = grads
            print(json.dumps({"variant": name, "load": load,
                              "assignments": sum(LOADS[load]),
                              "ms": median_ms(run, (x, w, *mats, idx),
                                              args.reps)}), flush=True)
    if args.pieces:
        pool = 2 * held * args.rows
        rows = jnp.asarray(rng.standard_normal((pool, d)), jnp.float32)
        for load in ("even", "none"):
            n = min(sum(LOADS[load]), pool)
            token = jnp.asarray(np.concatenate([
                rng.integers(0, t, n), t + np.arange(pool - n)]), jnp.int32)
            pieces = {
                "gather": jax.jit(lambda tok: x.at[tok].get(
                    mode="fill", fill_value=0)),
                "scatter_add": jax.jit(lambda tok: r.at[tok].add(
                    rows, mode="drop")),
                "scatter_add_unique": jax.jit(lambda tok: r.at[tok].add(
                    rows, mode="drop", unique_indices=True)),
            }
            for name, fn in pieces.items():
                print(json.dumps({"piece": name, "rows": pool, "real": n,
                                  "ms": median_ms(fn, (token,), args.reps)}),
                      flush=True)
    if args.other:
        spec = importlib.util.spec_from_file_location("other", args.other)
        other = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(other)
        run = program(other.held_experts)
        for load, idx in idxs.items():
            _, grads = run(x, w, *mats, idx)
            off = [float(jnp.linalg.norm((a - b).astype(jnp.float32).ravel())
                         / jnp.maximum(jnp.linalg.norm(
                             b.astype(jnp.float32).ravel()), 1e-30))
                   for a, b in zip(reference[load], grads)]
            print(json.dumps({"variant": "other", "load": load,
                              "assignments": sum(LOADS[load]),
                              "ms": median_ms(run, (x, w, *mats, idx),
                                              args.reps),
                              "new_vs_other_grad_error": dict(zip(
                                  ("x", "weights", "gate", "up", "down"),
                                  off))}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
