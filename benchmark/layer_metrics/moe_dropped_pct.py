"""Assignments the held experts dropped over those that arrived, in the
steps the window's `fit` read at its log boundaries: the program's own
counters (`LoopStats.counters`, fed from the step's metrics; the expert
layer counts the rows its loop processed).  Reads 0: the layer has no
capacity to overflow.  None where the program counts nothing (the parent
of the PR that added the counters)."""
NAME, UNIT = "moe_dropped_pct", "%"


def applies(cell):
    from benchmark.layer_metrics import moe_experts_ms
    return moe_experts_ms.has_expert_layer(cell)


def read(ctx):
    from benchmark.layer_metrics import _step_layers
    stats = _step_layers.loop_stats(ctx)
    counters = (stats or {}).get("counters") or {}
    dropped = counters.get("moe/dropped")
    arrived = counters.get("moe/assignments_mean")
    if not dropped or not arrived or not arrived["total"]:
        return None
    config = ctx["cell"]["config"]
    layers = sum(ffn == "moe" for _, ffn in
                 ctx["cell"]["family"].layer_kinds(config))
    held = config["num_experts"] * layers
    return 100.0 * dropped["total"] / (arrived["total"] * held)
