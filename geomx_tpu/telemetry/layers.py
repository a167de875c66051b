"""The program's own names for the step's layers and the host loop's phases.

Everything here hangs off the one span primitive, ``profile_scope``
(utils/profiler.py).  Inside the jitted step a scope tags every op traced
under it, and the compiled program's HLO text carries the tag as
``metadata={op_name="jit(..)/../step/optimizer/add"}`` on each
instruction.  A profile names device events by instruction, so joining a
trace's seconds-by-instruction with :func:`op_layers` of the same
program charges every device op to a scope of :data:`SCOPES`, and through
it to a layer of PERF.md's list.  On the host the same names are spans in
the profiler's trace, and :class:`LoopStats` keeps what the spans of
``Trainer.fit`` would show in counters that need no profiler session.

The vocabulary (docs/telemetry.md has the operator's table):

- ``step/*``: the boundaries of ``_device_step`` (train/step.py);
- ``compress/*``, ``bsc/*``, ``<axis>_allreduce/bucket<i>``: the
  compression engine (compression/) inside ``step/sync_grads``;
- ``<axis>_pipeline/*``: the pipelined sync engine (sync/pipeline.py);
- ``collective/worker``, ``collective/dc``: the tier collectives;
- ``kda/*``, ``mla/*``, ``gqa/*``, ``moe/*``, ``lm/loss``: a decoder's
  layers inside ``step/forward_backward`` (models/kimi_linear.py,
  models/afmoe.py, models/decoder.py);
- ``attn/core``: the attention kernels and what surrounds them
  (ops/flash_attention.fused_attention), forward and backward;
- ``train/step``, ``fit/*``, ``loader/*``: host spans of the loop.
"""

from __future__ import annotations

import contextlib
import re
import time
from typing import Dict, Iterable, NamedTuple, Optional

from geomx_tpu.utils.profiler import profile_scope

# scope prefix -> PERF.md layer.  A scope is two path components
# (``step/optimizer``); a prefix that ends in ``/`` or in a name's stem
# (``dc_allreduce/bucket``) covers a family.
SCOPES = (
    ("step/forward_backward", "step program"),
    # a decoder's layers, opened inside step/forward_backward
    # (models/kimi_linear.py, models/afmoe.py, models/decoder.py,
    # ops/kda.py)
    ("kda/proj", "step program"),
    ("kda/scan", "kernels"),
    ("mla/proj", "step program"),
    ("mla/attention", "kernels"),
    ("gqa/proj", "step program"),
    ("gqa/window", "kernels"),
    ("gqa/global", "kernels"),
    ("moe/route", "step program"),
    ("moe/experts", "step program"),
    # a pool's row gathers and scatter-adds, opened inside moe/experts
    # (ops/held_experts.py)
    ("moe/dispatch", "step program"),
    ("moe/shared", "step program"),
    ("lm/loss", "step program"),
    # attention's core, forward and backward, opened by
    # ops/flash_attention.fused_attention; in a decoder it nests inside
    # mla/attention, gqa/window or gqa/global
    ("attn/core", "kernels"),
    ("step/optimizer", "step program"),
    ("step/metrics", "step program"),
    ("step/sync_grads", "sync algorithm"),
    ("step/sync_params", "sync algorithm"),
    ("step/sync_model_state", "sync algorithm"),
    ("dc_pipeline/", "sync algorithm"),
    ("worker_pipeline/", "sync algorithm"),
    ("compress/", "compression engine"),
    ("dc_allreduce/bucket", "compression engine"),
    ("worker_allreduce/bucket", "compression engine"),
    ("bsc/", "kernels"),
    ("collective/worker", "collectives / mesh"),
    ("collective/dc", "collectives / mesh"),
    ("train/step", "entry / host loop"),
    ("fit/", "entry / host loop"),
    ("loader/", "entry / host loop"),
)

FORWARD_BACKWARD = "step/forward_backward"
SYNC_GRADS = "step/sync_grads"
OPTIMIZER = "step/optimizer"

# the phases of one iteration of ``Trainer.fit``, in order
FIT_PHASES = ("fit/next_batch", "fit/dispatch", "fit/log_sync",
              "fit/log_fn", "fit/eval")


def layer_of(scope: str) -> Optional[str]:
    """The layer of one two-component scope, or None outside the
    vocabulary."""
    for prefix, layer in SCOPES:
        if scope.startswith(prefix):
            return layer
    return None


class OpLayer(NamedTuple):
    """One instruction of the compiled step.  ``scope``: the vocabulary
    scopes on the instruction's name stack, outermost first, joined by
    ``/`` (``step/sync_grads/dc_allreduce/bucket3/bsc/select_pack``);
    ``""`` where its ``op_name`` holds none of them, None where it has no
    ``op_name`` at all (the compiler made it: a copy, a layout change);
    ``layer``: the innermost scope's layer;
    ``direction``: ``forward`` / ``backward`` under
    ``step/forward_backward`` (JAX's own ``transpose(`` wrapper marks the
    backward pass), else None."""
    scope: Optional[str]
    layer: Optional[str]
    direction: Optional[str]


UNSCOPED = OpLayer("", None, None)
UNNAMED = OpLayer(None, None, None)


def classify_op_name(op_name: str) -> OpLayer:
    """``jit(step)/step/forward_backward/transpose(jvp())/dot_general`` ->
    ``("step/forward_backward", "step program", "backward")``."""
    parts = op_name.split("/")
    found, layer = [], None
    i = 0
    while i + 1 < len(parts):
        pair = parts[i] + "/" + parts[i + 1]
        hit = layer_of(pair)
        if hit is None:
            i += 1
            continue
        found.append(pair)
        layer = hit
        i += 2
    if not found:
        return UNSCOPED
    direction = None
    if found[0] == FORWARD_BACKWARD:
        direction = "backward" if "transpose(" in op_name else "forward"
    return OpLayer("/".join(found), layer, direction)


_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s*\(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([^\s=]+)\s*=\s*")
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
# attributes through which a computation is *run* as events of its own; a
# fusion's ``calls=`` and a reduce's ``to_apply=`` are fused into one event
_CALLED = re.compile(
    r"(?:body|condition|true_computation|false_computation)=%?([^\s,)}]+)"
    r"|branch_computations=\{([^}]*)\}")
_CALL_TARGET = re.compile(r"(?:to_apply|calls)=%?([^\s,)}]+)")
# never an event on the device: no time can be charged to them
_NO_EVENT = frozenset(("parameter", "constant", "tuple", "get-tuple-element",
                       "bitcast"))


def op_layers(hlo_text: Iterable[str]) -> Dict[str, OpLayer]:
    """{instruction name: OpLayer} for the instructions of the optimized
    HLO that run as device events of their own: those of the entry
    computation and of the bodies it calls (``call``, ``while``,
    ``conditional``, async wrappers), transitively.  Names inside fused
    computations repeat across the module and are left out; a fusion
    carries one ``op_name``, its root's, so ops that straddle a scope
    boundary are charged to the root's scope.  An instruction without an
    ``op_name`` inside the body of a ``while`` or a branch of a
    ``conditional`` is the loop's own cost (the copies to and from fast
    memory that the compiler schedules around a scan's steps) and takes
    the loop's scope, layer and direction; at entry level it stays
    :data:`UNNAMED`.

    ``hlo_text``: ``compiled.as_text()`` or any iterable of its lines (a
    BERT-large step with a kernel call per bucket runs to hundreds of
    megabytes; lines are read once, in order)."""
    if isinstance(hlo_text, str):
        hlo_text = _lines(hlo_text)
    computations: Dict[str, list] = {}
    entry = current = None
    for line in hlo_text:
        if current is None:
            head = _COMPUTATION.match(line)
            if head:
                current = head.group(1)
                computations[current] = []
                if line.startswith("ENTRY"):
                    entry = current
            continue
        if line.startswith("}"):
            current = None
            continue
        ins = _INSTRUCTION.match(line)
        if not ins:
            continue
        # cut the operand list and attributes from the result type, whose
        # tuple shapes hold parentheses of their own
        rest = line[ins.end():]
        opcode = _OPCODE.search(rest.split("metadata=", 1)[0])
        opcode = opcode.group(1) if opcode else ""
        if opcode in _NO_EVENT:
            continue
        name = _OP_NAME.search(rest)
        bodies, wrapped = [], []         # of a loop or branch / of a call
        for m in _CALLED.finditer(rest):
            if m.group(1):
                bodies.append(m.group(1))
            else:
                bodies.extend(
                    t.strip().lstrip("%") for t in m.group(2).split(","))
        if opcode == "call" or opcode.endswith("-start"):
            wrapped = _CALL_TARGET.findall(rest)
        computations[current].append(
            (ins.group(1), name.group(1) if name else "", bodies, wrapped))
    table: Dict[str, OpLayer] = {}
    todo, seen = [(entry, UNNAMED)] if entry else [], set()
    while todo:
        comp, loop = todo.pop()
        if comp in seen or comp not in computations:
            continue
        seen.add(comp)
        for name, op_name, bodies, wrapped in computations[comp]:
            table[name] = classify_op_name(op_name) if op_name else loop
            # only a scope of the vocabulary is handed down
            inner = table[name] if table[name].scope else UNNAMED
            todo.extend((body, inner) for body in bodies)
            todo.extend((target, UNNAMED) for target in wrapped)
    return table


def _lines(text: str):
    """The lines of a text that is too large to split into a list."""
    return (m.group(0) for m in re.finditer(r"[^\n]+", text))


class LoopStats:
    """Always-on counters of one ``Trainer.fit``: for each phase of
    :data:`FIT_PHASES` its count, total seconds, longest single
    occurrence and the step that fell on; and the loop's wall seconds.
    Updated in place as each phase ends, so a fit left by an exception
    (the benchmark's ``log_fn`` ends its window so) leaves them whole.
    ``step`` is the iteration in flight, set by the loop; the spans a
    phase opens carry it.  ``counters``: what the model counts in a step
    (``metrics["counters"]``: named scalars, e.g. the assignments an
    expert layer dropped), added up over the steps read at log
    boundaries: count, total, last, max."""

    def __init__(self):
        self.step = 0
        self.steps = 0
        self.wall_s = 0.0
        self.phases = {name: {"count": 0, "total_s": 0.0, "max_s": 0.0,
                              "max_step": -1} for name in FIT_PHASES}
        self.counters: Dict[str, dict] = {}
        self._start = time.perf_counter()

    def count(self, values: dict) -> None:
        """One step's counters, as read at a log boundary."""
        for name, value in values.items():
            value = float(value)
            rec = self.counters.setdefault(
                name, {"count": 0, "total": 0.0, "last": 0.0, "max": value})
            rec["count"] += 1
            rec["total"] += value
            rec["last"] = value
            rec["max"] = max(rec["max"], value)

    @contextlib.contextmanager
    def phase(self, name: str):
        """Time one occurrence of ``name`` and open its span."""
        with profile_scope(name, "host", args={"step": self.step}):
            begin = time.perf_counter()
            try:
                yield
            finally:
                end = time.perf_counter()
                rec = self.phases[name]
                rec["count"] += 1
                rec["total_s"] += end - begin
                if end - begin > rec["max_s"]:
                    rec["max_s"] = end - begin
                    rec["max_step"] = self.step
                self.wall_s = end - self._start

    def as_dict(self) -> dict:
        out = {"steps": self.steps, "wall_s": self.wall_s,
               "phases": {k: dict(v) for k, v in self.phases.items()}}
        if self.counters:
            out["counters"] = {k: dict(v) for k, v in self.counters.items()}
        return out


# What the last ``Trainer.fit`` of this process left behind, for a reader
# that no longer holds the trainer (the chip benchmark drops its trainer
# before per-layer metrics are read): its LoopStats, and the abstract
# signature (shapes, dtypes, shardings) of the step's arguments, from
# which a fresh trainer can lower the same program again.
_last = {"loop_stats": None, "step_signature": None}


def record_fit(loop_stats: LoopStats, step_signature) -> None:
    _last["loop_stats"] = loop_stats
    _last["step_signature"] = step_signature


def last_loop_stats() -> Optional[LoopStats]:
    return _last["loop_stats"]


def last_step_signature():
    """(state, x, y) as ``jax.ShapeDtypeStruct`` trees, or None before the
    first fit."""
    return _last["step_signature"]
