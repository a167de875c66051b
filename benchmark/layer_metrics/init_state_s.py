"""Seconds inside `Trainer.init_state`, from the program's lifecycle
record: the first occurrence of span `setup/init_state` (the jitted
`model.init`, the optimizer's and the sync algorithm's state one eager op
a leaf, the placing of the state on the mesh).  The benchmark's `SETUP`
item `state_init_s` holds it and the seeded weights made after it."""
NAME, UNIT = "init_state_s", "s"


def applies(cell):
    return True


def read(ctx):
    from benchmark.layer_metrics import _lifecycle
    return _lifecycle.span_seconds(ctx, "setup/init_state")
