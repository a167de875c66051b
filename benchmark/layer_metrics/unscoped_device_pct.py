"""Device time of the ops the program's table cannot charge to a scope of
its vocabulary (not in the table, or in it under no scope: compiler-made
copies, a program loaded from a cache entry that holds no scopes) over
the device time of all ops: how far `forward_ms`, `backward_ms`,
`optimizer_ms` and `compress_engine_ms` can be trusted."""
NAME, UNIT = "unscoped_device_pct", "%"


def applies(cell):
    return True


def read(ctx):
    from benchmark.layer_metrics import _step_layers
    seconds = _step_layers.layer_seconds(ctx)
    if seconds is None or not seconds["total"]:
        return None
    return (100.0 * (seconds["unscoped"] + seconds["unknown"])
            / seconds["total"])
