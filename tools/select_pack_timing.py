"""Time the Bi-Sparse select/pack on the chip, at a bucket's real size.

For each ``n:count`` (bucket elements : calls) and each shape of
gradient it makes ``count`` gradients on the device and times one jitted
program that compresses all of them against one momentum buffer and one
error accumulator, ending in ``block_until_ready``.  A call to the device
costs the host about 0.6 ms whatever it does, so ``count`` calls share
one program and the line gives milliseconds per call.  Every variant
computes the sampled boundary too (``compress`` does, and the kernel
needs it):

- ``fused``: ``ops.bsc_pallas.bsc_select_pack``, as the engine calls it;
- ``other``: ``bsc_select_pack`` of the module given with ``--other`` (a
  parent commit's file), to compare schedules;
- ``xla``: ``ops.bsc_pallas.select_pack_ref``, the jnp chain (mask,
  cumsum, scatter of all n indices), what runs off a TPU.

The gradients (``--shapes``):

- ``uniform``: normal everywhere, so every 1,024 elements hold pairs;
- ``rows``: zero outside a quarter of its 1,024-wide rows, the
  embedding's shape of sparsity (a batch holds at most 8,192 of 30,522
  tokens), dense inside them;
- ``overflow``: four times larger wherever the boundary's probe does not
  look, so about half the bucket lies above the boundary and the first k
  in index order take every slot.

One JSON line per size and shape on stdout, medians over ``--reps`` runs
after a warm-up; every variant's four outputs are compared with the jnp
chain's bit for bit (``*_unequal`` counts the elements that differ) and
the line carries the placement's schedule (``tiles``, ``out_blocks``,
``visits``).  ROADMAP D3: a kernel that does not beat XLA's own fusion at
real sizes is deleted with its flag; this is the measurement that rule
asks for (PERF.md).

    python tools/select_pack_timing.py 31254528:4 4194304:16 7040:64
"""
import argparse
import functools
import importlib.util
import json
import math
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SHAPES = ("uniform", "rows", "overflow")
RATIO = 0.01


def median_ms(fn, args, reps):
    import jax
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(1e3 * (time.perf_counter() - t0))
    return statistics.median(times)


def gradients(shape, n, count, seed):
    """``(g[count, n], u[n], v[n])`` on the device."""
    import jax
    import jax.numpy as jnp
    from geomx_tpu.ops.sampled_topk import sample_positions

    kg, ku, kv, kr = jax.random.split(jax.random.PRNGKey(seed), 4)
    g = jax.random.normal(kg, (count, n), jnp.float32)
    u = 0.1 * jax.random.normal(ku, (n,), jnp.float32)
    v = 0.2 * jax.random.normal(kv, (n,), jnp.float32)
    if shape == "rows":
        held = jax.random.uniform(kr, (-(-n // 1024),)) < 0.25
        held = jnp.repeat(held, 1024)[:n]
        g, u, v = g * held, u * held, v * held
    elif shape == "overflow":
        probed = jnp.zeros((n,), bool).at[
            jnp.asarray(sample_positions(n), jnp.int32)].set(True)
        g = jnp.where(probed, g, 4.0 * g)
    return g, u, v


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("sizes", nargs="+", help="n:count, elements:calls")
    parser.add_argument("--reps", type=int, default=7)
    parser.add_argument("--shapes", default=",".join(SHAPES))
    parser.add_argument("--other", default=None,
                        help="path of another bsc_pallas.py to time too")
    parser.add_argument("--skip", default="",
                        help="comma-separated variants to leave out")
    parser.add_argument("--interpret", action="store_true",
                        help="rehearse on the CPU: no times, kernels "
                             "interpreted")
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from geomx_tpu.ops import bsc_pallas

    on_chip = jax.default_backend() == "tpu"
    if not on_chip and not args.interpret:
        print("not a TPU: a time from here is not a device time",
              file=sys.stderr)
        return 1
    kernels = {"fused": bsc_pallas.bsc_select_pack}
    if args.other:
        spec = importlib.util.spec_from_file_location("other_bsc", args.other)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        kernels["other"] = module.bsc_select_pack
    skip = set(filter(None, args.skip.split(",")))

    def with_boundary(select, k):
        def one(g, u, v):
            thr = bsc_pallas.sampled_boundary_guv(g, u, v, k)
            return select(g, u, v, thr, k)
        return one

    def unequal(got, want):
        """Elements whose bits differ, over all calls, by output."""
        return [sum(jnp.sum(jax.lax.bitcast_convert_type(a[i], jnp.int32)
                            != jax.lax.bitcast_convert_type(b[i], jnp.int32))
                    for a, b in zip(got, want)) for i in range(4)]

    ok = True
    for size in args.sizes:
        n, count = (int(x) for x in size.split(":"))
        k = max(1, math.ceil(n * RATIO))
        tiles, out_blocks, out_rows = bsc_pallas.select_pack_shape(n, k)

        def every_call(one):
            return jax.jit(lambda g, u, v: [one(g[c], u, v)
                                            for c in range(count)])

        # one program a variant and size, whatever the gradient's shape
        variants = {"xla": every_call(
            with_boundary(bsc_pallas.select_pack_ref, k))}
        for name, kernel in kernels.items():
            variants[name] = every_call(with_boundary(functools.partial(
                kernel, interpret=args.interpret), k))
        for shape in args.shapes.split(","):
            g, u, v = gradients(shape, n, count, seed=n % 9973)
            line = {"n": n, "k": k, "shape": shape, "calls": count,
                    "tiles": tiles, "out_blocks": out_blocks,
                    "reps": args.reps,
                    "device": jax.devices()[0].device_kind}
            if tiles > 1:
                thr = bsc_pallas.sampled_boundary_guv(g[0], u, v, k)
                line["visits"] = int(bsc_pallas.place_visits(
                    *bsc_pallas.select_pack_counts(
                        g[0], u, v, thr, interpret=args.interpret),
                    k, out_blocks, out_rows * 128)[2][0])
            want = None
            for name, fn in variants.items():
                if name in skip and name != "xla":
                    continue
                t0 = time.perf_counter()
                got = jax.block_until_ready(fn(g, u, v))
                line[name + "_first_run_s"] = time.perf_counter() - t0
                if want is None:
                    want = got
                    line["emitted"] = int(jnp.sum(got[0][1] >= 0))
                else:
                    diff = [int(x) for x in
                            jax.jit(unequal)(got, want)]
                    line[name + "_unequal"] = diff
                    ok = ok and not any(diff)
                del got
                if on_chip and name not in skip:
                    line[name + "_ms_per_call"] = median_ms(
                        fn, (g, u, v), args.reps) / count
            del want
            print(json.dumps(line), flush=True)
    return 0 if ok else 2


if __name__ == "__main__":
    sys.exit(main())
