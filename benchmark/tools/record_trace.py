"""Records the small trace kept in benchmark/testdata/ and prints what is
in it.  Run on the chip:  chiprun -- python3 benchmark/tools/record_trace.py

A known program: five calls of one jitted step (two matmuls, one
elementwise op, one reduction), a 30 ms host sleep between calls 2 and 3
under a TraceAnnotation, python tracing off so that the file stays small.
"""
import glob
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main():
    import jax
    import jax.numpy as jnp

    out = os.path.join("chiprun_out", "trace_small")
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    print("devices", jax.devices())

    @jax.jit
    def small_step(a, b):
        c = jnp.dot(a, b)
        d = jnp.tanh(c) + 1.0
        e = jnp.dot(d, b)
        return e, jnp.sum(e.astype(jnp.float32))

    a = jnp.ones((2048, 2048), jnp.bfloat16)
    b = jnp.ones((2048, 2048), jnp.bfloat16) * 0.001
    jax.block_until_ready(small_step(a, b))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(out, profiler_options=opts)
    for i in range(5):
        with jax.profiler.TraceAnnotation("bench/segment", segment=i):
            a, s = small_step(a, b)
            jax.block_until_ready(s)
        if i == 1:
            with jax.profiler.TraceAnnotation("bench/host_sleep"):
                time.sleep(0.03)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    print("trace", path, os.path.getsize(path), "bytes")
    shutil.copy(path, os.path.join(out, "small.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        print("PLANE", repr(plane.name))
        for line in plane.lines:
            evs = list(line.events)
            print("  LINE", repr(line.name), len(evs))
            for ev in evs[:12]:
                stats = {k: v for k, v in list(ev.stats)[:8]}
                print("     ", repr(ev.name), ev.start_ns, ev.duration_ns,
                      stats)


if __name__ == "__main__":
    main()
