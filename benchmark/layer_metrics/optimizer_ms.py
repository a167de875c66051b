"""Device time per step of the optimizer: the ops the program's table
puts under `step/optimizer` (`tx.update` and `apply_updates`, or the fused
apply).
Source: the trace's seconds by instruction joined with the program's
table of its own step (`_step_layers.py`)."""
NAME, UNIT = "optimizer_ms", "ms"


def applies(cell):
    return True


def read(ctx):
    from benchmark.layer_metrics import _step_layers
    return _step_layers.ms_per_step(ctx, "optimizer")
