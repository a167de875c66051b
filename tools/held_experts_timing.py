"""Time the held experts' layer on the chip, at a benchmark cell's size.

Makes ``T`` tokens of width ``d`` (bfloat16), ``E`` held experts of width
``f`` and, for each named load, a routing in which held expert ``e``
gets exactly the load's ``e``-th count of assignments (each on a token
of its own, the other choices on absent experts); times one jitted
program that runs `ops.held_experts.held_experts` forward and backward
(value and every gradient) and prints milliseconds per call, medians
over ``--reps`` runs after a warm-up.  ``--config <file>`` takes ``T``,
``d``, ``f``, ``E``, the router's width and the experts a token from a
configuration's file (`benchmark/configs/*.json`: the decoder cells'; a
file with a ``moe_latent_size`` gives that as ``d``, one whose
``mlp_hidden_act`` is ``relu2`` un-gated experts; the held experts are the
file's ``num_experts`` or, in a DeepSeek-style key set, its
``n_routed_experts``; `mellum2-12b-ep4.json`
gives 16 held of 64 at 2 held picks a token, 2,048 assignments an expert
under even routing, a first pool of 65,536 places;
`glm-4.7-flash-ep8.json` 8 held of 64 at 0.5 held picks a token, 1,024
an expert, a first pool of 16,384), and
the tile and the first pool from its ``program``; the loads are the Kimi
cell's patterns (8 held experts, 512 assignments each under even routing)
repeated over the held experts and scaled to the file's even load.

- ``new``: the module as it stands, once for each ``--shapes`` entry
  ``rows[:pool]`` (the kernels' tile, the first pool's places; no pool:
  2 E rows); ``new@k,n/k,n``: the same with other caps on the kernels'
  tiles (`GMM_TILES` / `TGMM_TILES`);
- ``other``: ``held_experts`` of the module given with ``--other`` (a
  parent commit's file), with every gradient's distance from ``new``'s
  over its norm.

``--pieces`` times the first pool's row gather (XLA's) and its scatter-add
(XLA's, and the kernel of `ops/moe_rows_pallas.py`) alone at four loads,
many calls in one program with the operands made inside it (a host-timed
call costs ~0.6 ms whatever it does, and reads cold operands), every row
of the kernel's compared with XLA's (exit 2 where they differ), and XLA's
scatter-add at 2,304 wide over 8,192, 32,768 and 65,536 places
(`SCATTER_PLACES`: the Kimi cell's first pool, the Mellum cell's even load
and its first pool), with the line through the three that says what part
of a call is fixed; then the
plan's pieces at the cells' 131,072 and 360,448 routed assignments
(`PLAN_SIZES`): the sort that carries index and weight against `argsort`
and a 1-D gather of the weights, and the sort that brings `dweights` back
against the 1-D scatter, made and timed the same way, outputs compared bit
for bit, a ``Verdict`` line each.  A step of
the cell calls the layer four times (its four expert layers), each forward
and backward once; the rematerialised forward needs the sorted assignments
again and, where the block has a post-norm that reads `y` (the Trinity
cell), the walk too.  One JSON line per variant and load.

    python tools/held_experts_timing.py --other _scratch/held_experts_old.py
    python tools/held_experts_timing.py \
        --config benchmark/configs/trinity-mini-ep8.json \
        --shapes "512:32768 1024 512"
    python tools/held_experts_timing.py \
        --config benchmark/configs/mellum2-12b-ep4.json --loads even,none
    python tools/held_experts_timing.py \
        --config benchmark/configs/glm-4.7-flash-ep8.json
"""
import argparse
import importlib.util
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# at 8 held experts and an even load of 512 each (the Kimi cell)
LOADS = {
    "even": [512] * 8,
    "seeded": [4, 60, 200, 350, 500, 700, 900, 3406],
    "emptied": [1, 2, 0, 3, 1, 2, 1, 3],
    "none": [0] * 8,
    "doubled": [1003] * 8,
    "one_takes_all": [15557, 100, 30, 5, 0, 2, 1, 60],
}


def loads(held: int, even: int, tokens: int) -> dict:
    """LOADS' patterns over `held` experts at `even` assignments each; an
    expert gets a token at most once."""
    return {name: [min(tokens, round(pattern[e % 8] * even / 512))
                   for e in range(held)]
            for name, pattern in LOADS.items()}


def routing(rng, tokens, top_k, counts, absent):
    """idx [T, k]: expert e on counts[e] distinct tokens, each in the
    token's next free choice (more experts may be held than a token has
    choices)."""
    import numpy as np
    idx = np.full((tokens, top_k), absent, np.int32)
    used = np.zeros(tokens, np.int64)
    for e, count in enumerate(counts):
        free = np.flatnonzero(used < top_k)
        chosen = rng.choice(free, size=min(count, free.size), replace=False)
        idx[chosen, used[chosen]] = e
        used[chosen] += 1
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


PIECE_LOADS = ("none", "even", "seeded", "one_takes_all")
CALLS = 8       # of a piece in one timed program


def every_call(move):
    """One program of (token, sizes, *operands): `CALLS` times make the
    operands (one elementwise pass behind an optimization barrier: what
    the step's layers leave the move) and move them (None: make them
    only)."""
    import jax
    import jax.numpy as jnp

    def one(c, acc, token, sizes, operands):
        step = 1.0 + c.astype(jnp.float32) * 2.0 ** -10
        made = jax.lax.optimization_barrier(tuple(
            (a * step.astype(a.dtype)) for a in operands))
        moved = jax.lax.optimization_barrier(
            made[0] if move is None else move(*made, token, sizes))
        return acc + moved[0, 0].astype(jnp.float32)
    return jax.jit(lambda token, sizes, *operands: jax.lax.fori_loop(
        0, CALLS, lambda c, acc: one(c, acc, token, sizes, operands),
        jnp.zeros((), jnp.float32)))


def pieces(args, x, r, by_load, pool, rng) -> bool:
    """The first pool's two moves alone: XLA's row gather, and the
    scatter-add as XLA's (plain and with `unique_indices`) beside the
    kernel (`ops/moe_rows_pallas.py`), into a made y and into the zeros a
    walk starts from (the kernel's relayout of y is then nothing).
    `CALLS` calls share one jitted program that makes each call's operands
    inside it (`every_call`), and the making, timed alone, is taken off.
    The gather as the layer does it (every id a token's, nothing to fill)
    beside the parent's (ids past the runs out of range, filled with
    zeros by a select over the pool).  One JSON line a load and piece;
    False where the kernel's rows differ from XLA's."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from geomx_tpu.ops import moe_rows_pallas as rows_ops

    t, d = x.shape
    interpret = jax.default_backend() != "tpu"
    out0 = jnp.asarray(rng.standard_normal((pool, d)), jnp.float32)

    def sorted_places(counts):
        """(token [pool], sizes [E]): each expert's run on tokens of its
        own, cut at the pool's end; ids past the runs point outside."""
        ends = np.minimum(np.cumsum(counts), pool)
        sizes = np.diff(ends, prepend=0)
        runs = [rng.choice(t, size, replace=False) for size in sizes]
        pad = t + np.arange(pool - ends[-1])
        return (jnp.asarray(np.concatenate(runs + [pad]), jnp.int32),
                jnp.asarray(sizes, jnp.int32))

    gathers = {
        "xla": lambda x_, token, sizes: x_.at[token % t].get(
            mode="promise_in_bounds"),
        "xla_fill": lambda x_, token, sizes: x_.at[token].get(
            mode="fill", fill_value=0)}
    scatters = {
        "xla": lambda y, out, token, sizes: rows_ops.row_scatter_add_ref(
            y, out, token, None),
        "xla_unique": lambda y, out, token, sizes: y.at[token].add(
            out, mode="drop", unique_indices=True),
        "kernel": lambda y, out, token, sizes: rows_ops.moe_row_scatter_add(
            y, out, token, sizes, interpret=interpret)}
    # as a walk's first pool does it: into the zeros y starts from
    zeros = lambda move: lambda out, token, sizes: move(
        jnp.zeros((t, d), jnp.float32), out, token, sizes)
    ok = True
    for piece, moves, operands in (
            ("gather", gathers, (x,)),
            ("scatter_add", scatters, (r, out0)),
            ("scatter_add_into_zeros",
             {name: zeros(move) for name, move in scatters.items()},
             (out0,))):
        make = every_call(None)
        programs = {name: every_call(move) for name, move in moves.items()}
        for load in PIECE_LOADS:
            token, sizes = sorted_places(by_load[load])
            real = int(jnp.sum(sizes))
            line = {"piece": piece, "load": load, "places": pool, "d": d,
                    "dtype": str(operands[0].dtype), "real": real}
            if "kernel" in moves:
                line["tile"] = rows_ops.tile_rows(pool, d)
                want = jax.jit(moves["xla"])(*operands, token, sizes)
                gap = jnp.abs(
                    jax.jit(moves["kernel"])(*operands, token, sizes) - want)
                line["kernel_unequal"] = int(jnp.sum(gap > 0))
                line["kernel_largest_gap"] = float(jnp.max(gap))
                # a token that sits in three runs or more may get its
                # addends in another order
                ok = ok and line["kernel_largest_gap"] <= 1e-5 * max(
                    1.0, float(jnp.max(jnp.abs(want))))
            if not interpret:
                made = median_ms(make, (token, sizes, *operands), args.reps)
                line["make_ms"] = made / CALLS
                for name, fn in programs.items():
                    line[name + "_ms"] = (median_ms(
                        fn, (token, sizes, *operands), args.reps)
                        - made) / CALLS
                line["xla_ns_a_place"] = 1e6 * line["xla_ms"] / pool
                if "kernel" in moves and real:
                    line["kernel_ns_a_row"] = 1e6 * line["kernel_ms"] / real
            print(json.dumps(line), flush=True)
    return ok


# XLA's scatter-add where the row kernel is not taken (hidden 2,304): the
# Kimi cell's first pool, the Mellum cell's even load and its first pool
SCATTER_PLACES = (8192, 32768, 65536)
SCATTER_WIDTH = 2304


def scatter_by_places(args, tokens, rng) -> None:
    """XLA's scatter-add of `[places, 2304]` float32 rows into the zeros a
    walk starts from, for each of `SCATTER_PLACES`, half the places holding
    a row (tokens drawn with repeats, as two held picks a token give) and
    none, timed as `pieces` times its moves; then the least-squares line
    ms = fixed + places x slope through the three sizes a load: a fixed
    part that is most of a small pool's call says a split or chunked way
    back would pay it again, a line through zero that it would cost
    nothing.  One JSON line a size and load, one `Line` a load; off a TPU
    the programs run and no time is printed."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from geomx_tpu.ops import moe_rows_pallas as rows_ops

    on_chip = jax.default_backend() == "tpu"
    move = lambda out, token, sizes: rows_ops.row_scatter_add_ref(
        jnp.zeros((tokens, SCATTER_WIDTH), jnp.float32), out, token, None)
    make, scatter = every_call(None), every_call(move)
    times = {"none": [], "half": []}
    for places in SCATTER_PLACES:
        out = jnp.asarray(rng.standard_normal((places, SCATTER_WIDTH)),
                          jnp.float32)
        for load, real in (("none", 0), ("half", places // 2)):
            token = jnp.asarray(np.concatenate([
                rng.integers(0, tokens, real),
                tokens + np.arange(places - real)]), jnp.int32)
            sizes = jnp.asarray([real], jnp.int32)
            line = {"piece": "xla_scatter_add_by_places", "places": places,
                    "d": SCATTER_WIDTH, "tokens": tokens, "load": load,
                    "real": real}
            if on_chip:
                made = median_ms(make, (token, sizes, out), args.reps)
                line["make_ms"] = made / CALLS
                line["xla_ms"] = (median_ms(scatter, (token, sizes, out),
                                            args.reps) - made) / CALLS
                line["xla_ns_a_place"] = 1e6 * line["xla_ms"] / places
                times[load].append(line["xla_ms"])
            else:
                jax.block_until_ready(scatter(token, sizes, out))
            print(json.dumps(line), flush=True)
    if on_chip:
        for load, ms in times.items():
            slope, fixed = np.polyfit(SCATTER_PLACES, ms, 1)
            print("Line " + json.dumps({
                "piece": "xla_scatter_add_by_places", "load": load,
                "fixed_ms": float(fixed), "ns_a_place": 1e6 * float(slope),
                "fixed_share_of_smallest": float(fixed) / ms[0]}),
                flush=True)


# (tokens, experts a token, held, routed over): the Trinity cell's routed
# assignments (the Kimi cell's are as many) and the Nemotron cell's
PLAN_SIZES = ((16384, 8, 16, 128), (16384, 22, 8, 512))


def plan_pieces(args, rng) -> bool:
    """What `ops/held_experts` pays by the routed assignment, alone: the
    plan's sort with its payload (`sort3`: key, index, weight) against
    `argsort` + the weights' 1-D gather (and `sort2`, the argsort alone),
    and `dweights`' way back as a sort by `order` against the 1-D scatter.
    `CALLS` calls in one program that makes each call's operands (the held
    experts' ids turned, the weights scaled, the permutation shifted), the
    making taken off.  False where a pair's outputs differ in any bit."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def sort3(key, w):
        return lax.sort((key, lax.iota(jnp.int32, key.size), w),
                        num_keys=1, is_stable=True)[1:]

    def argsort_gather(key, w):
        order = jnp.argsort(key, stable=True)
        return order, w[order]

    def sort2(key, w):                  # the argsort alone
        return jnp.argsort(key, stable=True), w

    def scatter(order, dw):
        return (jnp.zeros(dw.shape, dw.dtype).at[order].set(
            dw, unique_indices=True),)

    def sort_back(order, dw):
        return (lax.sort((order, dw), num_keys=1, is_stable=False)[1],)

    def every_plan_call(move, make):
        def one(c, acc, *operands):
            made = lax.optimization_barrier(make(c, *operands))
            moved = lax.optimization_barrier(
                made if move is None else move(*made))
            return acc + sum(m[0].astype(jnp.float32) for m in moved)
        return jax.jit(lambda *operands: lax.fori_loop(
            0, CALLS, lambda c, acc: one(c, acc, *operands),
            jnp.zeros((), jnp.float32)))

    scale = lambda c, w: w * (1.0 + c.astype(jnp.float32) * 2.0 ** -10)
    on_chip = jax.default_backend() == "tpu"
    ok = True
    for tokens, top_k, held, router in PLAN_SIZES:
        n = tokens * top_k
        idx = jnp.asarray(routing(
            rng, tokens, top_k, loads(held, n // router, tokens)["seeded"],
            router - 1)).reshape(-1)
        key = jnp.where(idx < held, idx, held).astype(jnp.int32)
        w = jnp.asarray(rng.uniform(0.1, 0.5, n), jnp.float32)
        order = jnp.argsort(key, stable=True)
        turned = lambda c, key_, w_: (
            jnp.where(key_ == held, held, (key_ + c) % held), scale(c, w_))
        shifted = lambda c, order_, dw: ((order_ + c) % n, scale(c, dw))
        # (piece, (what the layer did, what it does, others), ...)
        for piece, moves, make, operands in (
                ("plan_sort", (argsort_gather, sort3, sort2), turned,
                 (key, w)),
                ("dweights_back", (scatter, sort_back), shifted,
                 (order, w))):
            old, new = (move.__name__ for move in moves[:2])
            unequal = sum(
                int(jnp.sum(lax.bitcast_convert_type(a, jnp.int32)
                            != lax.bitcast_convert_type(b, jnp.int32)))
                for a, b in zip(*(jax.jit(move)(*operands)
                                  for move in moves[:2])))
            ok = ok and not unequal
            line = {"piece": piece, "assignments": n, "held": held,
                    "arrived": int(jnp.sum(key < held)),
                    "unequal": unequal}
            if on_chip:
                made = median_ms(every_plan_call(None, make), operands,
                                 args.reps)
                line["make_ms"] = made / CALLS
                for move in moves:
                    line[move.__name__ + "_ms"] = (median_ms(
                        every_plan_call(move, make), operands, args.reps)
                        - made) / CALLS
                line["ns_an_index_saved"] = 1e6 * (
                    line[old + "_ms"] - line[new + "_ms"]) / n
            print(json.dumps(line), flush=True)
            if on_chip:
                print("Verdict " + json.dumps({
                    "piece": piece, "assignments": n,
                    old + "_ms": line[old + "_ms"],
                    new + "_ms": line[new + "_ms"],
                    "faster": min(old, new,
                                  key=lambda name: line[name + "_ms"]),
                    "ops/held_experts.py": new}), flush=True)
    return ok


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--config", default=None,
                        help="a decoder configuration's file: sizes, tile "
                             "and pool come from it")
    parser.add_argument("--tokens", type=int, default=16384)
    parser.add_argument("--hidden", type=int, default=2304)
    parser.add_argument("--width", type=int, default=1024)
    parser.add_argument("--held", type=int, default=8)
    parser.add_argument("--router", type=int, default=256)
    parser.add_argument("--shapes", default=None,
                        help="`rows[:pool]` variants, separated by spaces; "
                             "default: the configuration's, else 512")
    parser.add_argument("--reps", type=int, default=5)
    parser.add_argument("--other", default=None,
                        help="path of another held_experts.py to time too")
    parser.add_argument("--tiles", default="",
                        help="more caps to try, `k,n/k,n` (gmm/tgmm), "
                             "separated by spaces")
    parser.add_argument("--loads", default=",".join(LOADS))
    parser.add_argument("--pieces", action="store_true",
                        help="time a pool's gather and scatter-add alone, "
                             "XLA's scatter-add at 2,304 by places, and the "
                             "plan's sorts against the 1-D gather and "
                             "scatter they replaced")
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from geomx_tpu.ops import held_experts as ours

    ok = True
    rng = np.random.default_rng(0)
    top_k, held, router, gated = 8, args.held, args.router, True
    t, d, f = args.tokens, args.hidden, args.width
    shapes = args.shapes or "512"
    if args.config:
        with open(args.config) as fh:
            config = json.load(fh)
        top_k = config.get("num_experts_per_tok",
                           config.get("num_experts_per_token"))
        # the held experts' count is the key the file's `reduced` lists
        held = config.get("n_routed_experts", config.get("num_experts"))
        router = config["router_experts"]
        t = config["per_chip_batch"] * config["sequence_length"]
        d = config.get("moe_latent_size") or config["hidden_size"]
        f = config["moe_intermediate_size"]
        gated = config.get("mlp_hidden_act") != "relu2"
        run_keys = config.get("program", {})
        pool = run_keys.get("expert_pool_places")
        shapes = args.shapes or (
            str(run_keys.get("expert_block_rows", 512))
            + (f":{pool}" if pool else ""))
    shapes = [tuple(int(v) for v in entry.split(":"))
              for entry in shapes.split()]
    # a shape's first pool: its own, else the module's default 2 E rows
    first_pool = lambda shape: (shape[1:] or (2 * held * shape[0],))[0]
    by_load = loads(held, t * top_k // router, t)
    x = jnp.asarray(rng.standard_normal((t, d)), jnp.bfloat16)
    w = jnp.asarray(rng.uniform(0.1, 0.5, (t, top_k)), jnp.float32)
    r = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
    mats = [jnp.asarray(rng.standard_normal(s) * 0.02, jnp.float32)
            for s in ((held, d, f), (held, d, f), (held, f, d))]
    if not gated:
        mats[0] = None          # relu(x W_up)^2 W_down: no gate
    idxs = {name: jnp.asarray(routing(rng, t, top_k, by_load[name],
                                      router - 1))
            for name in args.loads.split(",")}

    def program(fn, rows, pool=None):
        # a module of before the first pool had a size of its own takes none
        more = () if pool is None else (None, pool)

        def loss(x_, w_, gate, up, down, idx):
            y, counts, dropped = fn(x_, idx, w_, gate, up, down, 0, rows,
                                    *more)
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
        for shape in shapes:
            run = program(ours.held_experts, *shape)
            for load, idx in idxs.items():
                (_, (counts, dropped)), grads = run(x, w, *mats, idx)
                assert int(dropped) == 0 and [int(c) for c in counts] \
                    == by_load[load], (load, counts, dropped)
                if name == "new" and shape == shapes[0]:
                    reference[load] = grads
                print(json.dumps({
                    "variant": name, "rows": shape[0],
                    "pool": first_pool(shape),
                    "load": load, "assignments": sum(by_load[load]),
                    "ms": median_ms(run, (x, w, *mats, idx), args.reps)}),
                    flush=True)
    if args.pieces:
        ok = pieces(args, x, r, by_load, first_pool(shapes[0]), rng)
        scatter_by_places(args, t, rng)
        ok = plan_pieces(args, rng) and ok
    if args.other:
        spec = importlib.util.spec_from_file_location("other", args.other)
        other = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(other)
        run = program(other.held_experts, *shapes[0])
        for load, idx in idxs.items():
            _, grads = run(x, w, *mats, idx)
            # un-gated experts have no gate and no gradient for it
            off = {name: float(
                jnp.linalg.norm((a - b).astype(jnp.float32).ravel())
                / jnp.maximum(jnp.linalg.norm(
                    b.astype(jnp.float32).ravel()), 1e-30))
                for name, a, b in zip(("x", "weights", "gate", "up", "down"),
                                      reference[load], grads)
                if a is not None}
            print(json.dumps({"variant": "other", "load": load,
                              "assignments": sum(by_load[load]),
                              "ms": median_ms(run, (x, w, *mats, idx),
                                              args.reps),
                              "new_vs_other_grad_error": off}),
                  flush=True)
    return 0 if ok else 2


if __name__ == "__main__":
    sys.exit(main())
