"""Seconds of Python the step program cost before XLA was asked for it:
JAX's trace of the step function to a jaxpr and the jaxpr's lowering to a
module, from the program's `CompileLog` (the step function's occurrence
inside `fit/first_dispatch`).  Paid on a warm cache as on a cold one; it
grows with the model's code, not with its sizes."""
NAME, UNIT = "step_trace_lower_s", "s"


def applies(cell):
    return True


def read(ctx):
    from benchmark.layer_metrics import _lifecycle
    step = _lifecycle.step_program(ctx)
    return step["trace_s"] + step["lower_s"] if step else None
