"""Records the small traces kept in benchmark/testdata/ and prints what is
in them.  Run on the chip:

    chiprun -- python3 benchmark/tools/record_trace.py [small|scan]

`small`, a known program: five calls of one jitted step (two matmuls, one
elementwise op, one reduction), a 30 ms host sleep between calls 2 and 3
under a TraceAnnotation, python tracing off so that the file stays small.

`scan`, a known program with a loop: three calls of one jitted step that
holds a matmul, a `lax.scan` of eight iterations (a matmul, a tanh and a
reduction each) and a reduction after it.

Either way every event of the `XLA Ops` line is printed with the depth at
which it is nested in the events before it (a `while` encloses its body's
ops there), and the spans' sum beside the reducer's busy time and per-op
sum.
"""
import glob
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def small_program():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def small_step(a, b):
        c = jnp.dot(a, b)
        d = jnp.tanh(c) + 1.0
        e = jnp.dot(d, b)
        return e, jnp.sum(e.astype(jnp.float32))

    def drive(a, b):
        for i in range(5):
            with jax.profiler.TraceAnnotation("bench/segment", segment=i):
                a, s = small_step(a, b)
                jax.block_until_ready(s)
            if i == 1:
                with jax.profiler.TraceAnnotation("bench/host_sleep"):
                    time.sleep(0.03)
    return small_step, drive


def scan_program():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def scan_step(a, b):
        def body(c, _):
            c = jnp.tanh(jnp.dot(c, b))
            return c, jnp.sum(c.astype(jnp.float32))
        c, sums = jax.lax.scan(body, jnp.dot(a, b), None, length=8)
        return c, jnp.sum(sums) + jnp.sum(c.astype(jnp.float32))

    def drive(a, b):
        for i in range(3):
            with jax.profiler.TraceAnnotation("bench/segment", segment=i):
                a, s = scan_step(a, b)
                jax.block_until_ready(s)
    return scan_step, drive


def print_nesting(events, limit=80):
    """Every event of one line in order of its start, indented by how many
    earlier events enclose it."""
    stack = []
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2]))[:limit]:
        while stack and stack[-1] <= start:
            stack.pop()
        print("      OP", "  " * len(stack) + name[:70], start, dur)
        stack.append(start + dur)


def main(which="small"):
    import jax
    import jax.numpy as jnp

    step, drive = {"small": small_program, "scan": scan_program}[which]()
    out = os.path.join("chiprun_out", "trace_" + which)
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    print("devices", jax.devices())
    a = jnp.ones((2048, 2048), jnp.bfloat16)
    b = jnp.ones((2048, 2048), jnp.bfloat16) * 0.001
    jax.block_until_ready(step(a, b))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2 if which == "small" else 1
    jax.profiler.start_trace(out, profiler_options=opts)
    drive(a, b)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    print("trace", path, os.path.getsize(path), "bytes")
    shutil.copy(path, os.path.join(out, which + ".xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        print("PLANE", repr(plane.name))
        for line in plane.lines:
            evs = list(line.events)
            print("  LINE", repr(line.name), len(evs))
            if line.name == "XLA Ops":
                print_nesting([(ev.name, ev.start_ns, ev.duration_ns)
                               for ev in evs])
                continue
            for ev in evs[:12]:
                stats = {k: v for k, v in list(ev.stats)[:8]}
                print("     ", repr(ev.name), ev.start_ns, ev.duration_ns,
                      stats)
    from benchmark import trace_reduce
    chips, host = trace_reduce.read_planes(path)
    summary = trace_reduce.reduce_planes(chips, host)
    print("SUMS", {"spans_s_whole_trace": sum(
                       b - a for lines in chips.values()
                       for _, a, b in lines.get("XLA Ops", [])) / 1e9,
                   "busy_s": summary["busy_s_busiest"],
                   "by_op_s_sum": sum(summary["by_op_s"].values()),
                   "by_family_s": summary["by_family_s"]})


if __name__ == "__main__":
    main(*sys.argv[1:2])
