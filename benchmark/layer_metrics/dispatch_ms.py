"""Host milliseconds per step inside the `train_step` call
(`fit/dispatch`) in the untraced window, from the program's always-on
`LoopStats`: the time to enqueue a step, which the device hides unless it
has drained."""
NAME, UNIT = "dispatch_ms", "ms"


def applies(cell):
    return True


def read(ctx):
    from benchmark.layer_metrics import _step_layers
    stats = _step_layers.loop_stats(ctx)
    if not stats or not stats["steps"]:
        return None
    return 1e3 * stats["phases"]["fit/dispatch"]["total_s"] / stats["steps"]
