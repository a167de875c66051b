"""Device time per step of the backward pass: the ops the program's table
puts under `step/forward_backward` whose name stack holds JAX's
`transpose(` wrapper.  A fusion counts under its root's scope.
Source: the trace's seconds by instruction joined with the program's
table of its own step (`_step_layers.py`)."""
NAME, UNIT = "backward_ms", "ms"


def applies(cell):
    return True


def read(ctx):
    from benchmark.layer_metrics import _step_layers
    return _step_layers.ms_per_step(ctx, "backward")
