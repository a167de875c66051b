"""Programs XLA was asked for up to the mark `fit/first_boundary` (the
first step's results on the host), whoever asked: the trainer's, the
eager one-offs of `init_state` and the benchmark's own readers alike,
from the program's `CompileLog`.  Each costs a lowering and a cache read
on a warm start."""
NAME, UNIT = "setup_programs", "count"


def applies(cell):
    return True


def read(ctx):
    from benchmark.layer_metrics import _lifecycle
    found = _lifecycle.setup_occurrences(ctx)
    return None if found is None else len(found)
