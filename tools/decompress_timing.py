"""Time the Bi-Sparse decompress on the chip, at a bucket's real size.

For each ``n:m:count`` (bucket elements : pairs : calls) it makes
``count`` sets of pairs the way the sampled selection on one party hands
them over (distinct ascending indices, a sentinel tail), and times one
jitted program that decompresses all of them, ending in
``block_until_ready``.  A call to the device costs the host about 0.6 ms
whatever it does, so ``count`` calls share one program and the line
gives milliseconds per call:

- ``fused``: ``ops.bsc_pallas.bsc_scatter_add``, as the engine calls it;
- ``fused_shuffled``: the same on the pairs in random order (what
  ``lax.top_k`` or several parties hand over: pays the sort);
- ``xla`` / ``xla_shuffled``: ``ops.bsc_pallas.scatter_add_ref``, XLA's
  own scatter-add (what runs off a TPU), on the same two orders;
- ``other``: ``bsc_scatter_add(vals, idx, n)`` of the module given with
  ``--other`` (a parent commit's file), to compare schedules.

One JSON line per size on stdout, medians over ``--reps`` runs after a
warm-up; every variant's result is compared with XLA's.  ROADMAP S4: a
kernel that does not beat XLA's own fusion at real sizes is deleted
with its flag; this is the measurement that rule asks for (PERF.md).

    python tools/decompress_timing.py 31254528:312546:4 4194304:41944:16
"""
import argparse
import importlib.util
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


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
    parser.add_argument("sizes", nargs="+",
                        help="n:m:count, elements:pairs:calls")
    parser.add_argument("--reps", type=int, default=7)
    parser.add_argument("--emitted", type=float, default=0.957,
                        help="share of the m slots that hold a real pair")
    parser.add_argument("--other", default=None,
                        help="path of another bsc_pallas.py to time too")
    parser.add_argument("--skip", default="",
                        help="comma-separated variants to leave out")
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from geomx_tpu.ops.bsc_pallas import bsc_scatter_add, scatter_add_ref

    if jax.default_backend() != "tpu":
        print("not a TPU: a time from here is not a device time",
              file=sys.stderr)
        return 1
    kernels = {"fused": bsc_scatter_add}
    if args.other:
        spec = importlib.util.spec_from_file_location("other_bsc", args.other)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        kernels["other"] = module.bsc_scatter_add
    skip = set(filter(None, args.skip.split(",")))
    for size in args.sizes:
        n, m, count = (int(x) for x in size.split(":"))
        rng = np.random.RandomState(n % 9973)
        real = int(m * args.emitted)
        idx = np.full((count, m), -1, np.int32)
        for row in idx:
            row[:real] = np.sort(rng.choice(n, real, replace=False))
        vals = np.where(idx >= 0, rng.normal(size=idx.shape),
                        0.0).astype(np.float32)
        perm = rng.permutation(m)
        ascending = (jnp.asarray(vals), jnp.asarray(idx))
        shuffled = (jnp.asarray(vals[:, perm]), jnp.asarray(idx[:, perm]))

        def every_call(one):
            return jax.jit(lambda v, i: [one(v[c], i[c], n)
                                         for c in range(count)])

        variants = {
            "xla": (every_call(scatter_add_ref), ascending),
            "xla_shuffled": (every_call(scatter_add_ref), shuffled),
            "fused": (every_call(kernels["fused"]), ascending),
            "fused_shuffled": (every_call(kernels["fused"]), shuffled),
        }
        if "other" in kernels:
            variants["other"] = (every_call(kernels["other"]), ascending)
        line = {"n": n, "m": m, "calls": count, "real_pairs": real,
                "reps": args.reps, "device": jax.devices()[0].device_kind}
        want = None
        for name, (fn, operands) in variants.items():
            if name in skip:
                continue
            t0 = time.perf_counter()
            got = jax.block_until_ready(fn(*operands))
            line[name + "_first_run_s"] = time.perf_counter() - t0
            if want is None:
                want = got
            line[name + "_max_abs_gap"] = max(
                float(jnp.max(jnp.abs(g - w))) for g, w in zip(got, want))
            del got
            line[name + "_ms_per_call"] = median_ms(
                fn, operands, args.reps) / count
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
