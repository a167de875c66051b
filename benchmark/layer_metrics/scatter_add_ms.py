"""Device time per step of the Bi-Sparse decompress: every instruction
the program's table puts under a scope that contains `bsc/scatter_add`,
which `BiSparseCompressor.decompress` opens around its whole fused
branch.  So the number keeps its meaning whatever the decompress is made
of: a Pallas kernel, XLA ops (a sort of the pairs, the schedule's
searches) or both.  Its floor is writing the n floats and reading the m
pairs once (PERF.md, section 5).
Source: the trace's seconds by instruction joined with the program's
table of its own step (`_step_layers.py`); the join is done here."""
NAME, UNIT = "scatter_add_ms", "ms"
SCOPE = "bsc/scatter_add"


def applies(cell):
    return cell["traffic"]["geoconfig"]["compression"].startswith("bsc")


def read(ctx):
    from benchmark.layer_metrics import _step_layers
    trace = ctx.get("trace")
    if not trace or not trace["steps"]:
        return None
    table = _step_layers.step_table(ctx)
    if table is None:
        return None
    seconds = 0.0
    for name, op_seconds in trace["by_op_s"].items():
        entry = table.get(name)
        if entry is not None and entry.scope and SCOPE in entry.scope:
            seconds += op_seconds
    return 1e3 * seconds / trace["steps"]
