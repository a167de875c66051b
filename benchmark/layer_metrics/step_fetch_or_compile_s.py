"""Seconds JAX's backend stage took for the step program, from the
program's `CompileLog` (the step function's occurrence inside
`fit/first_dispatch`): XLA's compile on a cold cache; on a warm one the
persistent cache's read of the executable and its load onto the chip
(`cache` and `retrieval_s` on the `LIFECYCLE` line say which)."""
NAME, UNIT = "step_fetch_or_compile_s", "s"


def applies(cell):
    return True


def read(ctx):
    from benchmark.layer_metrics import _lifecycle
    step = _lifecycle.step_program(ctx)
    return step["backend_s"] if step else None
