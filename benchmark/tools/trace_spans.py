"""Opens one traced run of a cell by hand: which of the program's host
spans enclose each idle gap of the device, and what the host recorded per
step.  Run on the chip:

    chiprun -- python3 benchmark/tools/trace_spans.py --workload <cell> \
        --seed <n> [--host-level <l>]

It sets a cell up as `benchmark/run.py` does (same trainer, weights,
data, first steps), runs the cell's traced segments and prints two things
read from the one `.xplane.pb`, so host spans and device ops share a
clock:

- GAP lines: for each idle gap of the device over a millisecond, the
  chain of spans on the host's `python3` line that enclose its middle,
  outermost first, each with its `step` where the program gave one
  (`train/step` > `fit/log_sync` > `np.asarray(jax.Array)`);
- HOST lines: the host plane's events by thread line and name: count,
  count per step, total and longest; the 40 largest by total time.

`--host-level` overrides the cell's `trace_host_level` in memory only
(resnet18-bsc-1c keeps 0 in its file because level 1 starves its loader:
this tool is how to look at why).
"""
import argparse
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def read_host_and_device(path):
    """(device ops [(start, end)], modules [(start, end)],
    host events [(line, name, start, end, stats)]) in nanoseconds."""
    import jax
    ops, modules, host = [], [], []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:0"):
            for line in plane.lines:
                if line.name not in ("XLA Ops", "XLA Modules"):
                    continue
                into = ops if line.name == "XLA Ops" else modules
                into.extend((ev.start_ns, ev.start_ns + ev.duration_ns)
                            for ev in line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    host.append((line.name, ev.name, ev.start_ns,
                                 ev.start_ns + ev.duration_ns,
                                 {k: v for k, v in ev.stats}))
    return ops, modules, host


def gaps_over(ops, lo, hi, least_ns):
    from benchmark.trace_reduce import merged
    busy = merged([(a, b) for a, b in ops if a >= lo and b <= hi])
    return [(b0, a1) for (_, b0), (a1, _) in zip(busy, busy[1:])
            if a1 - b0 >= least_ns]


def enclosing_chain(gap, host):
    """The spans of the `python3` line that hold the gap's middle,
    outermost first."""
    mid = (gap[0] + gap[1]) / 2
    chain = [(e - s, name, stats) for line, name, s, e, stats in host
             if line.startswith("python") and s <= mid <= e]
    return [{"span": name, "ms": ns / 1e6,
             **({"step": stats["step"]} if "step" in stats else {})}
            for ns, name, stats in sorted(chain, key=lambda c: -c[0])]


def host_summary(host, lo, hi, steps):
    table = {}
    for line, name, s, e, _stats in host:
        if e < lo or s > hi:
            continue
        rec = table.setdefault((line.split("/")[0], name), [0, 0.0, 0.0])
        rec[0] += 1
        rec[1] += e - s
        rec[2] = max(rec[2], e - s)
    rows = sorted(table.items(), key=lambda kv: -kv[1][1])[:40]
    return [{"line": line, "name": name[:80], "count": n,
             "per_step": n / steps, "total_ms": total / 1e6,
             "longest_ms": longest / 1e6}
            for (line, name), (n, total, longest) in rows]


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--host-level", type=int, default=None)
    args = parser.parse_args(argv)

    import numpy as np
    from benchmark import run, trace_reduce
    from benchmark.cells import Registry
    cell = Registry(ROOT).cell(args.workload)
    if args.host_level is not None:
        cell["workload"] = dict(cell["workload"],
                                trace_host_level=args.host_level)
    run.configure_compile_cache()
    run.require_chips(cell["chips"])
    config, traffic = cell["config"], cell["traffic"]
    rows = traffic["parties"] * traffic["workers"] * config["per_chip_batch"]
    trainer = run.build_trainer(cell)
    x, y = cell["family"].make_data(
        config, np.random.default_rng(args.seed),
        rows * (traffic["n_check"] + config["data_steps"]))
    state, shapes = run.initial_state(cell, trainer, args.seed, x[:2])
    state, _ = run.first_steps(cell, trainer, state, shapes, x, y, args.seed)
    trace_dir = os.path.join(ROOT, ".benchmark_cache", "trace_spans",
                             args.workload)
    run.traced_segments(cell, trainer, state, x, y, args.seed, trace_dir)

    path = trace_reduce.find_xplane(trace_dir)
    ops, modules, host = read_host_and_device(path)
    size = os.path.getsize(path)
    shutil.rmtree(trace_dir, ignore_errors=True)
    step_ns = max(b - a for a, b in modules)
    steps = [m for m in modules if m[1] - m[0] > 0.5 * step_ns]
    lo, hi = min(a for a, _ in steps), max(b for _, b in steps)
    print("TRACE " + json.dumps({
        "bytes": size, "steps": len(steps),
        "window_s": (hi - lo) / 1e9,
        "host_level": cell["workload"].get("trace_host_level", 1),
        "host_events": len(host)}))
    for gap in sorted(gaps_over(ops, lo, hi, 1e6),
                      key=lambda g: g[0] - g[1])[:12]:
        print("GAP " + json.dumps({
            "ms": (gap[1] - gap[0]) / 1e6, "at_s": (gap[0] - lo) / 1e9,
            "enclosed_by": enclosing_chain(gap, host)}))
    for row in host_summary(host, lo, hi, len(steps)):
        print("HOST " + json.dumps(row))
    return 0


if __name__ == "__main__":
    sys.exit(main())
