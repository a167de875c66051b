"""Seconds the window's `fit` waited on the loader's queue
(`fit/next_batch`) over the loop's wall seconds, from the program's
always-on `LoopStats`: needs no profiler session, so it is read in the
untraced window, also where the trace holds no host span."""
NAME, UNIT = "input_wait_pct", "%"


def applies(cell):
    return True


def read(ctx):
    from benchmark.layer_metrics import _step_layers
    stats = _step_layers.loop_stats(ctx)
    if not stats or not stats["wall_s"]:
        return None
    return (100.0 * stats["phases"]["fit/next_batch"]["total_s"]
            / stats["wall_s"])
