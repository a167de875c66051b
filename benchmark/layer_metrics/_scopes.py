"""Device milliseconds a step under one scope of the program's vocabulary:
every instruction the program's table of its own step (`_step_layers.py`)
puts under a scope path that contains `needle`, forward, rematerialised
and backward alike, summed from the trace's own times by instruction (a
`while`'s body counts once, PERF.md section 3).  None without a trace, or
where the program has no table or no such scope (the parent of the PR that
added it): the metric is then left out."""


def scope_ms(ctx, needle: str):
    from benchmark.layer_metrics import _step_layers
    trace = ctx.get("trace")
    if not trace or not trace["steps"]:
        return None
    table = _step_layers.step_table(ctx)
    if table is None:
        return None
    seconds, found = 0.0, False
    for name, op_seconds in trace["by_op_s"].items():
        entry = table.get(name)
        if entry is not None and entry.scope and needle in entry.scope:
            seconds += op_seconds
            found = True
    return 1e3 * seconds / trace["steps"] if found else None
