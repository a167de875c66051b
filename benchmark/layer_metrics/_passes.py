"""Device seconds of the traced window under `step/forward_backward` by
the pass the program's table gives each instruction (`OpLayer.pass_`:
`first` forward, the forward `recomputed` inside the backward, which JAX
names `rematted_computation`, and the `backward` proper), and the scope's
self time: the instructions whose scope path holds `step/forward_backward`
and nothing else (the backward's name stack repeats it), so what no scope
opened inside it names.  `forward_ms` is `first`; `backward_ms` is
`recomputed` + `backward`.  A fusion counts under its root's scope and
pass; a backward rule that recomputes for itself (the held experts', the
flash kernels') is `backward`: only `jax.checkpoint`'s copy is told apart.

Reuses the table and the trace's seconds by instruction that
`_step_layers.py` keeps in `ctx`, and prints one `PASSES` line a traced
run: ms a step by pass, the self time, and the 40 largest (scope, pass)
with `step/forward_backward` dropped from the path (`(self)` where
nothing is left).  A program whose table has no pass (the parent of the
PR that added it) gives the self time alone."""
import json

SECONDS = "step_pass_seconds"
SCOPE = "step/forward_backward"
SELF = "(self)"


def pass_seconds(ctx):
    """{`self`: seconds, and each pass of the program's: seconds}, or None
    without a trace or a table."""
    from benchmark.layer_metrics import _step_layers
    if SECONDS in ctx:
        return ctx[SECONDS]
    ctx[SECONDS] = None
    trace = ctx.get("trace")
    if not trace or not trace["steps"]:
        return None
    table = _step_layers.step_table(ctx)
    if table is None:
        return None
    out = dict.fromkeys(
        getattr(_step_layers.program_layers(), "PASSES", ()), 0.0)
    out["self"] = 0.0
    by_scope = {}
    for name, seconds in trace["by_op_s"].items():
        entry = table.get(name)
        if entry is None or not entry.direction:
            continue                    # not under step/forward_backward
        inner = "/".join(part.strip("/") for part in entry.scope.split(SCOPE)
                         if part.strip("/")) or SELF
        if inner == SELF:
            out["self"] += seconds
        which = getattr(entry, "pass_", None)
        if which in out:
            out[which] += seconds
        key = (inner, which or entry.direction)
        by_scope[key] = by_scope.get(key, 0.0) + seconds
    ctx[SECONDS] = out
    steps = trace["steps"]
    print("PASSES " + json.dumps({
        "ms_per_step": {k: 1e3 * v / steps for k, v in out.items()},
        "ms_per_step_by_scope_and_pass": [
            [scope, which, 1e3 * v / steps] for (scope, which), v in sorted(
                by_scope.items(), key=lambda kv: -kv[1])[:40]]}),
        flush=True)
    return out


def ms_per_step(ctx, key):
    seconds = pass_seconds(ctx)
    if seconds is None or key not in seconds:
        return None
    return 1e3 * seconds[key] / ctx["trace"]["steps"]
