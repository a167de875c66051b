"""Time the Bi-Sparse boundary probe on the chip, at a bucket's real size.

For each ``n:count`` (bucket elements : calls) and each shape of gradient
it times one jitted program that, ``count`` times over, MAKES g, u and v
(one elementwise pass each over three arrays made once outside, behind an
optimization barrier: what the step's flatten and last step's select/pack
leave a bucket's probe) and takes their boundary, ending in
``block_until_ready``.  A call to the device costs the host about 0.6 ms
whatever it does, so ``count`` calls share one program (one ``fori_loop``:
one compiled probe a program) and a line gives milliseconds per call, the
making, timed alone as ``make``, taken off:

- ``gathers``: ``ops.bsc_pallas.sampled_boundary_guv``, three XLA gathers
  at the probe's positions, what runs off a TPU and the oracle;
- ``kernel``: ``ops.bsc_pallas.bsc_sampled_boundary`` with the threshold
  out of the way, so ``bsc_boundary_probe`` streams the bucket at every
  size above the probe's (and no fetch at all up to it).

``door_takes`` says which of the two the engine's call
(``ops.dispatch.sampled_boundary``) takes at that size, by
``_PROBE_GATHER_ABOVE``.

The gradients (``--shapes``) are ``tools/select_pack_timing.py``'s
``uniform`` and ``rows``, and ``zero`` (boundary 0).  One JSON line per
size and shape on stdout, medians over ``--reps`` runs after a warm-up;
every variant's ``count`` boundaries are compared with the gathers' bit
for bit (``*_unequal`` counts the calls that differ; exit 2 where any
does).  Last, a ``VERDICT`` line by size: which of gathers and kernel is
faster there, and what the door takes.  ``_PROBE_GATHER_ABOVE`` is set
from that table (PERF.md section 5).

    python tools/boundary_timing.py 31254528:4 8388608:8 4194304:16 \\
        3145728:16 2359296:16 1048576:32 133120:64 7040:64
"""
import argparse
import functools
import json
import math
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

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


def base_arrays(shape, n, seed):
    """``(g0, u0, v0)`` [n] on the device; call c's operands are these
    scaled by factors near 1 that differ from call to call."""
    import jax
    import jax.numpy as jnp

    if shape == "zero":
        return (jnp.zeros((n,), jnp.float32),) * 3
    kg, ku, kv, kr = jax.random.split(jax.random.PRNGKey(seed), 4)
    g = jax.random.normal(kg, (n,), jnp.float32)
    u = 0.1 * jax.random.normal(ku, (n,), jnp.float32)
    v = 0.2 * jax.random.normal(kv, (n,), jnp.float32)
    if shape == "rows":
        held = jax.random.uniform(kr, (-(-n // 1024),)) < 0.25
        held = jnp.repeat(held.at[0].set(True), 1024)[:n]
        g, u, v = g * held, u * held, v * held
    return g, u, v


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("sizes", nargs="+", help="n:count, elements:calls")
    parser.add_argument("--reps", type=int, default=7)
    parser.add_argument("--shapes", default="uniform,rows")
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
    kernel = functools.partial(bsc_pallas.bsc_sampled_boundary,
                               interpret=args.interpret)
    boundaries = {
        "gathers": bsc_pallas.sampled_boundary_guv,
        "kernel": functools.partial(kernel, gather_above=2 ** 31),
    }

    def every_call(boundary, count, k):
        """One program: ``count`` times make g, u, v and take their
        boundary (``None``: make them only); [count] float32."""
        def one(c, out, g0, u0, v0):
            step = c.astype(jnp.float32) * 2.0 ** -10
            g, u, v = jax.lax.optimization_barrier(
                (g0 * (1.0 + step), u0 * (1.0 - step), v0 * (1.0 + 2 * step)))
            thr = (g[0] + u[0] + v[0] if boundary is None
                   else boundary(g, u, v, k))
            return out.at[c].set(thr)

        return jax.jit(lambda g0, u0, v0: jax.lax.fori_loop(
            0, count, functools.partial(one, g0=g0, u0=u0, v0=v0),
            jnp.zeros((count,), jnp.float32)))

    ok, verdicts = True, []
    for size in args.sizes:
        n, count = (int(x) for x in size.split(":"))
        k = max(1, math.ceil(n * RATIO))
        programs = {name: every_call(fn, count, k)
                    for name, fn in boundaries.items()}
        make = every_call(None, count, k)
        for shape in args.shapes.split(","):
            base = base_arrays(shape, n, seed=n % 9973)
            line = {"n": n, "k": k, "shape": shape, "calls": count,
                    "tiles": -(-n // bsc_pallas._TILE), "reps": args.reps,
                    "door_takes": ("dense" if n <= 8192 else "gathers"
                                   if n > bsc_pallas._PROBE_GATHER_ABOVE
                                   else "kernel"),
                    "device": jax.devices()[0].device_kind}
            if on_chip:
                line["make_ms_per_call"] = median_ms(
                    make, base, args.reps) / count
            want = None
            for name, fn in programs.items():
                t0 = time.perf_counter()
                got = jax.block_until_ready(fn(*base))
                line[name + "_first_run_s"] = time.perf_counter() - t0
                bits = jax.lax.bitcast_convert_type(got, jnp.int32)
                if want is None:
                    want = bits
                    line["boundary"] = float(got[0])
                else:
                    line[name + "_unequal"] = int(jnp.sum(bits != want))
                    ok = ok and line[name + "_unequal"] == 0
                if on_chip:
                    line[name + "_ms_per_call"] = (
                        median_ms(fn, base, args.reps) / count
                        - line["make_ms_per_call"])
            print(json.dumps(line), flush=True)
            if on_chip and shape == args.shapes.split(",")[0]:
                verdicts.append({
                    "n": n, "gathers_ms": line["gathers_ms_per_call"],
                    "kernel_ms": line["kernel_ms_per_call"],
                    "faster": min(("gathers", "kernel"), key=lambda name:
                                  line[name + "_ms_per_call"]),
                    "door_takes": line["door_takes"]})
    for verdict in verdicts:
        print("VERDICT " + json.dumps(verdict), flush=True)
    return 0 if ok else 2


if __name__ == "__main__":
    sys.exit(main())
