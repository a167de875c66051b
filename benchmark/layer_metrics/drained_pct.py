"""Seconds the window's `fit` had nothing queued on the device
(`fit/drained`: from the return of a log boundary's `device_get` to the
return of the next `train_step` call) over the loop's wall seconds, from
the program's always-on `LoopStats`: `device_idle_pct`'s twin from the
untraced window.  It counts the tail of the dispatch call after the step
was enqueued and misses the results' way to the host."""
NAME, UNIT = "drained_pct", "%"


def applies(cell):
    return True


def read(ctx):
    from benchmark.layer_metrics import _step_layers
    stats = _step_layers.loop_stats(ctx)
    if not stats or not stats["wall_s"] or "fit/drained" not in stats:
        return None
    return 100.0 * stats["fit/drained"]["total_s"] / stats["wall_s"]
