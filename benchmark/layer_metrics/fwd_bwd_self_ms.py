"""Device time per step of the scope `step/forward_backward`'s self time:
the ops the program's table puts under that scope and under no scope
opened inside it, all three passes, the compiler-made instructions of the
loops that inherit the bare scope with them.  In an encoder the whole
model (it opens no scope of its own but attention's); in a decoder what
`block/norm`, `lm/embed` and the layers' scopes leave unnamed.
Source: `_passes.pass_seconds`."""
NAME, UNIT = "fwd_bwd_self_ms", "ms"


def applies(cell):
    return True


def read(ctx):
    from benchmark.layer_metrics import _passes
    return _passes.ms_per_step(ctx, "self")
