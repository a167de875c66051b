"""Device time per step of the recomputed forward pass: the ops the
program's table puts under `step/forward_backward` whose name stack holds
`rematted_computation` (`jax.checkpoint`'s copy of a rematerialised half,
inside the backward's `transpose(`): the part of `backward_ms` that is a
second forward.  A true 0 where nothing is rematerialised; what XLA
shares with the first pass or drops as dead is not in it.
Source: `_passes.pass_seconds`."""
NAME, UNIT = "recompute_ms", "ms"


def applies(cell):
    return True


def read(ctx):
    from benchmark.layer_metrics import _passes
    return _passes.ms_per_step(ctx, "recomputed")
