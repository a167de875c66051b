"""Seconds inside the trainer's first call of its step, from the program's
lifecycle record: span `fit/first_dispatch`, which holds the step's
trace, lowering, the executable's fetch from the cache or its compile,
its load and the enqueue.  The benchmark's `SETUP` item
`first_step_1_through_fit_s` holds it, the loader's start and the wait
for the step's results."""
NAME, UNIT = "first_dispatch_s", "s"


def applies(cell):
    return True


def read(ctx):
    from benchmark.layer_metrics import _lifecycle
    return _lifecycle.span_seconds(ctx, _lifecycle.FIRST_DISPATCH)
