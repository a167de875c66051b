"""Device time per step of the whole compression engine: every op the
program's table puts under `step/sync_grads`, named kernels and XLA ops
alike (boundary probe, copies, converts, bucket flatten and unflatten).
`compress_kernels_ms` beside it holds the named kernels only.
Source: the trace's seconds by instruction joined with the program's
table of its own step (`_step_layers.py`)."""
NAME, UNIT = "compress_engine_ms", "ms"


def applies(cell):
    return cell["traffic"]["geoconfig"]["compression"].startswith("bsc")


def read(ctx):
    from benchmark.layer_metrics import _step_layers
    return _step_layers.ms_per_step(ctx, "sync_grads")
