"""Joins the program's table of its own step (`Trainer.step_layers`: HLO
instruction -> scope of the program's vocabulary) with the trace's device
seconds by instruction (`ctx["trace"]["by_op_s"]`), and reads the host
loop's always-on counters (`LoopStats`) of the window's `fit`.

The trace file cannot give the scope itself: `jax.profiler.ProfileData`
drops the event metadata that holds it (`tf_op`), and the trace is gone
before metric files load.  The compiled step's HLO text names the scope
of every instruction, so a fresh trainer lowers the step again from the
signature the last `fit` recorded, which the persistent compile cache
answers.  Both results are kept in `ctx`, once a process.

A program without these (the parent of the PR that added them) gives
None everywhere, and the metrics built on this file are left out."""
import json

SECONDS = "step_layer_seconds"


def program_layers():
    """`geomx_tpu.telemetry.layers`, or None where the program has none."""
    try:
        from geomx_tpu.telemetry import layers
    except ImportError:
        return None
    return layers


def step_table(ctx):
    """{instruction name: OpLayer} of the cell's step program, or None."""
    if "step_layers" in ctx:
        return ctx["step_layers"]
    ctx["step_layers"] = None
    layers = program_layers()
    if layers is None or layers.last_step_signature() is None:
        return None
    from benchmark.run import build_trainer
    table = build_trainer(ctx["cell"]).step_layers(
        *layers.last_step_signature())
    ctx["step_layers"] = table["ops"]
    print("LAYERS_TABLE " + json.dumps(
        {k: table[k] for k in ("instructions", "unscoped", "unnamed",
                               "seconds")}), flush=True)
    return ctx["step_layers"]


def layer_seconds(ctx):
    """Device seconds of the traced window by where the program's table
    puts each instruction: `forward`, `backward` (under
    `step/forward_backward`, told apart by JAX's `transpose(` wrapper),
    `optimizer`, `sync_grads` (the whole engine: named kernels and XLA
    ops), `other_scoped`, `unscoped` (in the table under no scope of the
    vocabulary) and `unknown` (not in the table); `total` is their sum.
    None without a trace or a table."""
    if SECONDS in ctx:
        return ctx[SECONDS]
    ctx[SECONDS] = None
    trace = ctx.get("trace")
    if not trace or not trace["steps"]:
        return None
    table = step_table(ctx)
    if table is None:
        return None
    out = dict.fromkeys(("forward", "backward", "optimizer", "sync_grads",
                         "other_scoped", "unscoped", "unknown"), 0.0)
    by_scope = {}
    for name, seconds in trace["by_op_s"].items():
        entry = table.get(name)
        if entry is None:
            key = "unknown"
        elif not entry.scope:
            key = "unscoped"
        elif entry.direction:
            key = entry.direction
        elif entry.scope.startswith("step/optimizer"):
            key = "optimizer"
        elif entry.scope.startswith("step/sync_grads"):
            key = "sync_grads"
        else:
            key = "other_scoped"
        out[key] += seconds
        if entry is not None and entry.scope:
            # buckets are many: fold `bucket<i>` into one line
            scope = "/".join("bucket*" if part.startswith("bucket") else part
                             for part in entry.scope.split("/"))
            by_scope[scope] = by_scope.get(scope, 0.0) + seconds
    out["total"] = sum(out.values())
    ctx[SECONDS] = out
    steps = trace["steps"]
    print("LAYERS " + json.dumps({
        "ms_per_step": {k: 1e3 * v / steps for k, v in out.items()},
        "found_by_name_pct": 100.0 * (1.0 - out["unknown"] / out["total"])
        if out["total"] else None,
        "busy_ms_per_step": 1e3 * trace["busy_s_busiest"] / steps,
        "ms_per_step_by_scope": {
            k: 1e3 * v / steps for k, v in sorted(
                by_scope.items(), key=lambda kv: -kv[1])[:40]}}),
        flush=True)
    return out


def ms_per_step(ctx, key):
    seconds = layer_seconds(ctx)
    if seconds is None:
        return None
    return 1e3 * seconds[key] / ctx["trace"]["steps"]


def loop_stats(ctx):
    """The window's `LoopStats` as a dict, or None: `fit` keeps them
    whole when the benchmark's `log_fn` leaves it by an exception, and
    the window's is the last `fit` of the process."""
    if "loop_stats" in ctx:
        return ctx["loop_stats"]
    ctx["loop_stats"] = None
    layers = program_layers()
    stats = layers.last_loop_stats() if layers else None
    if stats is not None and stats.steps:
        ctx["loop_stats"] = stats.as_dict()
        print("LOOP_STATS " + json.dumps(ctx["loop_stats"]), flush=True)
    return ctx["loop_stats"]
