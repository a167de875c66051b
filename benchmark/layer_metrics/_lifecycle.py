"""Reads the program's lifecycle record (`geomx_tpu.telemetry.layers.
Lifecycle`, with the process's `CompileLog`) of the trainer that ran the
window: what set-up's stages and the step's first dispatch took, what
each program cost to trace, lower and fetch or compile, what the
allocator held at the edges of set-up and of the first step, and the
placed state's bytes a chip.

`last_lifecycle()` gives the record of the trainer whose `fit` ran last,
which is the window's, and not the fresh trainer that
`_step_layers.step_table` builds afterwards to lower the step again; that
lowering's events are in the `CompileLog` too, after the mark
`fit/first_boundary`, and `setup_occurrences` cuts there.  The record is
read once a process, kept in `ctx` and printed as one `LIFECYCLE` line.

A program without the record (the parent of the PR that added it) gives
None everywhere, and the metrics built on this file are left out."""
import json

GIB = 2.0 ** 30
FIRST_DISPATCH = "fit/first_dispatch"
FIRST_BOUNDARY = "fit/first_boundary"


def lifecycle(ctx):
    """The record as a dict (`Lifecycle.as_dict()`), or None."""
    if "lifecycle" in ctx:
        return ctx["lifecycle"]
    ctx["lifecycle"] = None
    from benchmark.layer_metrics import _step_layers
    layers = _step_layers.program_layers()
    last = getattr(layers, "last_lifecycle", None)
    record = last() if last else None
    if record is not None:
        ctx["lifecycle"] = record.as_dict()
        print("LIFECYCLE " + json.dumps(ctx["lifecycle"]), flush=True)
    return ctx["lifecycle"]


def span_seconds(ctx, name):
    """Seconds of the first occurrence of span `name`, or None."""
    record = lifecycle(ctx)
    span = record["spans"].get(name) if record else None
    return span["seconds"] if span else None


def step_program(ctx):
    """The step function's occurrence inside `fit/first_dispatch`: its
    `trace_s`, `lower_s`, `backend_s`, `cache`; or None."""
    record = lifecycle(ctx)
    return record.get("step_program") if record else None


def setup_occurrences(ctx):
    """The `CompileLog`'s occurrences up to the mark `fit/first_boundary`,
    whoever asked for them; None without the mark."""
    record = lifecycle(ctx)
    if not record or record.get("first_boundary_t") is None \
            or "programs" not in record:
        return None
    return [occ for occ in record["programs"]["occurrences"]
            if occ["t"] <= record["first_boundary_t"]]


def mark_bytes(ctx, mark, field):
    """`field` of the first reading under `mark`, or None (no such mark,
    or a backend without allocator statistics)."""
    record = lifecycle(ctx)
    rec = record["marks"].get(mark) if record else None
    return rec["first"][field] if rec else None
