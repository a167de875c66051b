"""Reduces a profiler trace (`.xplane.pb`) to the numbers the per-layer
metrics read: device busy and idle time, time per kernel, and the longest
idle gaps with what the host was doing in them.  Needs nothing but JAX (`jax.profiler.ProfileData`).

What a TPU trace holds (looked at by hand, TPU v5 lite, jax 0.9.0): one
plane `/device:TPU:<n>` per chip with the lines `XLA Modules` (one event
per execution of a jitted program, named `jit_<fn>(<hash>)`), `XLA Ops`
(one event per HLO instruction the core ran, in order, named by the
instruction's whole text: `%fusion.3 = bf16[...] fusion(...)`; a Pallas
kernel is `%<jitted function>.<n> = ... custom-call(...)`; a `%while` is
one event that encloses the events of its body's ops), and `Async
XLA Ops` (DMA and collective spans that run beside the core).  The plane
`/host:CPU` has one line per thread; Python-level spans
(`TraceAnnotation`, `PjitFunction(<fn>)`) are on the line `python3`.
Times are nanoseconds from the start of the trace; the device's and the
host's clocks agree to about a millisecond.
"""
from __future__ import annotations

import glob
import os
import re

_NAME = re.compile(r"^%?([^\s=]+)")
_SUFFIX = re.compile(r"\.\d+$")


def op_name(text: str) -> str:
    """`%bsc_select_pack.15 = f32[..] custom-call(..)` -> `bsc_select_pack.15`."""
    return _NAME.match(text).group(1)


def op_family(name: str) -> str:
    """`bsc_select_pack.15` -> `bsc_select_pack`."""
    return _SUFFIX.sub("", name)


def union_ns(intervals) -> float:
    total, end = 0.0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def merged(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def own_ns(events):
    """[(name, nanoseconds)] of one line's events: each event's span less
    the spans of the events nested directly in it.  On the `XLA Ops` line a
    `while` is one event that encloses the events of its body's ops, every
    iteration's (looked at on the chip, PERF.md, PR 26): summing spans
    would count a loop's time twice, the own times sum to the busy time."""
    out, open_ = [], []                 # open_: (end, index into out)
    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while open_ and open_[-1][0] <= a:
            open_.pop()
        if open_:
            out[open_[-1][1]][1] -= min(b, open_[-1][0]) - a
        out.append([name, b - a])
        open_.append((b, len(out) - 1))
    return out


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def read_planes(path: str):
    """({chip: {line: [(name, start_ns, end_ns)]}}, [(line, name, a, b)])."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    chips, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = chips.setdefault(plane.name, {})
            for line in plane.lines:
                lines[line.name] = [
                    (ev.name, float(ev.start_ns),
                     float(ev.start_ns) + float(ev.duration_ns))
                    for ev in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend((line.name, ev.name, float(ev.start_ns),
                             float(ev.start_ns) + float(ev.duration_ns))
                            for ev in line.events)
    return chips, host


def _attribute(gap, host):
    """The host span that covers most of the gap; of equals, the shortest.
    Python-level spans win over the runtime's own threads."""
    a, b = gap
    best, best_key = "no host span", (0.0, 0, 0.0)
    for line, name, s, e in host:
        cover = min(b, e) - max(a, s)
        if cover <= 0:
            continue
        key = (round(cover / (b - a), 2), line == "python3", -(e - s))
        if key > best_key:
            best, best_key = name, key
    return best


def step_module_of(lines: dict):
    """(name, events) of the jitted program that took most device time on
    this chip: the step."""
    modules = {}
    for name, a, b in lines.get("XLA Modules", []):
        modules.setdefault(name.split("(")[0], []).append((a, b))
    if not modules:
        return None, []
    name = max(modules, key=lambda k: sum(b - a for a, b in modules[k]))
    return name, sorted(modules[name])


def reduce_planes(chips: dict, host: list) -> dict:
    """The traced window is bounded to whole executions of the step
    program: from the start of the first to the end of the last on any
    chip.  What the device did before and after (the tracer starting and
    stopping, a transfer) is not the loop's."""
    if not chips:
        raise ValueError("the trace holds no /device:TPU plane")
    steps = {chip: step_module_of(lines) for chip, lines in chips.items()}
    spans = [ev for _, events in steps.values() for ev in events]
    if not spans:
        raise ValueError("no jitted program ran on a device in the trace")
    lo, hi = min(a for a, _ in spans), max(b for _, b in spans)
    inside = {chip: [(t, a, b) for t, a, b in lines.get("XLA Ops", [])
                     if a >= lo and b <= hi]
              for chip, lines in chips.items()}
    per_chip = {chip: union_ns([(a, b) for _, a, b in ops])
                for chip, ops in inside.items()}
    if not any(per_chip.values()):
        raise ValueError("no operation ran on a device in the trace")
    busiest = max(per_chip, key=per_chip.get)

    ops = inside[busiest]
    by_op, by_family = {}, {}
    for text, own in own_ns(ops):
        name = op_name(text)
        by_op[name] = by_op.get(name, 0.0) + own
        fam = op_family(name)
        by_family[fam] = by_family.get(fam, 0.0) + own

    busy = merged([(a, b) for _, a, b in ops])
    gaps = sorted(((b0, a1) for (_, b0), (a1, _) in zip(busy, busy[1:])),
                  key=lambda g: g[0] - g[1])[:10]
    step_module, events = steps[busiest]
    return {
        "chips": len(chips),
        "busiest_chip": busiest,
        "busy_s_mean": sum(per_chip.values()) / len(per_chip) / 1e9,
        "busy_s_busiest": per_chip[busiest] / 1e9,
        "window_s": (hi - lo) / 1e9,
        "by_op_s": {k: v / 1e9 for k, v in by_op.items()},
        "by_family_s": {k: v / 1e9 for k, v in by_family.items()},
        "step_module": step_module,
        "steps": len(events),
        "idle_gaps": [[_attribute(g, host), (g[1] - g[0]) / 1e9,
                       (g[0] - lo) / 1e9] for g in gaps],
    }


def reduce_trace(trace_dir_or_file: str) -> dict:
    path = trace_dir_or_file
    if os.path.isdir(path):
        path = find_xplane(path)
    return reduce_planes(*read_planes(path))


def breakdown(summary: dict) -> dict:
    top = sorted(summary["by_op_s"].items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[name, seconds]
                          for name, seconds, _at in summary["idle_gaps"]]}


def family_time_s(summary: dict, prefixes) -> float:
    """Device time of every op whose family starts with one of `prefixes`."""
    return sum(v for k, v in summary["by_family_s"].items()
               if k.startswith(tuple(prefixes)))
