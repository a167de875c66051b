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
- ``kda/*``, ``mla/*``, ``gqa/*``, ``ssd/*``, ``moe/*``, ``ffn/mlp``,
  ``block/norm``, ``lm/embed``, ``lm/loss``, ``loop/exit``, ``mtp/*``: a
  decoder's layers inside ``step/forward_backward``
  (models/kimi_linear.py, models/afmoe.py, models/nemotron_h.py,
  models/decoder.py);
- ``attn/core``: the attention kernels and what surrounds them
  (ops/flash_attention.fused_attention), forward and backward;
- ``train/step``, ``fit/*``, ``loader/*``: host spans of the loop;
- ``setup/*``, ``fit/first_dispatch``: host spans of a trainer's set-up
  (:class:`Lifecycle`).

Beside ``LoopStats`` stands the lifecycle record: :class:`CompileLog`
(what each program cost to trace, lower, fetch or compile, by function,
phase and step, from ``jax.monitoring``'s events) and :class:`Lifecycle`
(a trainer's set-up spans, the allocator's numbers at the edges of
set-up and of each ``fit``, the placed state's bytes a chip).  Both are
always on and cost nothing between compiles and between a ``fit``'s
edges.
"""

from __future__ import annotations

import contextlib
import re
import threading
import time
from typing import Callable, Dict, Iterable, NamedTuple, Optional

import jax

from geomx_tpu.utils.profiler import profile_scope

# scope prefix -> PERF.md layer.  A scope is two path components
# (``step/optimizer``); a prefix that ends in ``/`` or in a name's stem
# (``dc_allreduce/bucket``) covers a family.
SCOPES = (
    ("step/forward_backward", "step program"),
    # a decoder's layers, opened inside step/forward_backward
    # (models/kimi_linear.py, models/afmoe.py, models/nemotron_h.py,
    # models/decoder.py, ops/kda.py)
    ("kda/proj", "step program"),
    ("kda/scan", "kernels"),
    ("mla/proj", "step program"),
    ("mla/attention", "kernels"),
    ("gqa/proj", "step program"),
    ("gqa/window", "kernels"),
    ("gqa/global", "kernels"),
    # a Mamba-2 layer (models/nemotron_h.py, ops/ssd.py)
    ("ssd/proj", "step program"),
    ("ssd/scan", "kernels"),
    ("moe/route", "step program"),
    ("moe/experts", "step program"),
    # a pool's row gathers and scatter-adds, opened inside moe/experts
    # (ops/held_experts.py)
    ("moe/dispatch", "step program"),
    # the sorts of the routed assignments (the plan, and the weights'
    # gradient back) and the counts, opened inside moe/experts too
    ("moe/plan", "step program"),
    ("moe/shared", "step program"),
    # a LatentMoE's down- and up-projection around its routed experts
    ("moe/latent", "step program"),
    # the dense SwiGLU half of a block (models/decoder.FFNBranch)
    ("ffn/mlp", "step program"),
    # the residual stream's norms and adds: a half's norm, post-norm and
    # ``h + y``, the final norm, a prediction module's output norm
    # (models/decoder.py; a mixer's own norms stay in its ``*/proj``).  A
    # fusion carries its root's op_name: a norm XLA folds into a
    # neighbour's product is charged there, not here
    ("block/norm", "step program"),
    # the embedding's row gather, multiplier and cast, and through the
    # name stack the scatter-add of its gradient (DecoderLM.embed)
    ("lm/embed", "step program"),
    ("lm/loss", "step program"),
    # a looped stack's exit gate: its product, the exit distribution and
    # the entropy (models/decoder.DecoderLM.looped_loss); the T head passes
    # stand under lm/loss
    ("loop/exit", "step program"),
    # a multi-token-prediction module, around everything it runs (its
    # block's mla/*, moe/*, attn/core and its lm/loss nest inside and keep
    # their meaning), and inside it the join: two norms, the next token's
    # lookup, the joining matrix (models/decoder.py)
    ("mtp/module", "step program"),
    ("mtp/combine", "step program"),
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
    ("setup/", "entry / host loop"),
)

FORWARD_BACKWARD = "step/forward_backward"
# the three passes under step/forward_backward (OpLayer.pass_): the first
# forward, the forward recomputed inside the backward (JAX names it
# ``rematted_computation`` inside ``transpose(``: jax.checkpoint's
# transpose rule), and the backward proper
FIRST, RECOMPUTED, BACKWARD = "first", "recomputed", "backward"
PASSES = (FIRST, RECOMPUTED, BACKWARD)
_REMATTED = "rematted_computation"
SYNC_GRADS = "step/sync_grads"
OPTIMIZER = "step/optimizer"

# the phases of one iteration of ``Trainer.fit``, in order
FIT_PHASES = ("fit/next_batch", "fit/dispatch", "fit/log_sync",
              "fit/log_fn", "fit/eval")
DISPATCH, LOG_SYNC = "fit/dispatch", "fit/log_sync"
# no phase: the device with nothing queued, across the phases from a log
# boundary to the next dispatch (LoopStats.drained)
DRAINED = "fit/drained"

FIRST_DISPATCH = "fit/first_dispatch"
FIRST_BOUNDARY = "fit/first_boundary"
# the spans of a trainer's set-up, outermost first (Lifecycle): building
# the trainer; init_state and its three parts; the trainer's first call
# of its step (inside that iteration's fit/dispatch)
SETUP_SPANS = ("setup/build", "setup/init_state", "setup/model_init",
               "setup/state_init", "setup/replicate", FIRST_DISPATCH)
# where the allocator is read (Lifecycle.mark): five reads a fit
MEMORY_MARKS = ("setup/init_state:begin", "setup/init_state:end",
                FIRST_DISPATCH, FIRST_BOUNDARY, "fit/end")
OUTSIDE = "outside"


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
    backward pass), else None: ``backward`` holds the recomputed forward;
    ``pass_``: one of :data:`PASSES` under ``step/forward_backward``, which
    tells the recomputed forward from both, else None."""
    scope: Optional[str]
    layer: Optional[str]
    direction: Optional[str]
    pass_: Optional[str] = None


UNSCOPED = OpLayer("", None, None)
UNNAMED = OpLayer(None, None, None)


def classify_op_name(op_name: str) -> OpLayer:
    """``jit(step)/step/forward_backward/transpose(jvp())/dot_general`` ->
    ``("step/forward_backward", "step program", "backward", "backward")``;
    with ``/checkpoint/rematted_computation/`` behind the ``transpose(``
    the pass is ``recomputed`` and the direction still ``backward``."""
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
    direction = pass_ = None
    if found[0] == FORWARD_BACKWARD:
        direction = "backward" if "transpose(" in op_name else "forward"
        pass_ = (RECOMPUTED if _REMATTED in op_name
                 else BACKWARD if direction == "backward" else FIRST)
    return OpLayer("/".join(found), layer, direction, pass_)


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
    the loop's scope, layer, direction and pass; at entry level it stays
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


# The phase open on each thread, innermost last: (name, step) pairs that
# LoopStats.phase and Lifecycle.span push and pop.  CompileLog reads the
# innermost when JAX reports a compile, which is how a recompile gets its
# phase and step.
_open = threading.local()


@contextlib.contextmanager
def _opened(name: str, step):
    stack = getattr(_open, "stack", None)
    if stack is None:
        stack = _open.stack = []
    stack.append((name, step))
    try:
        yield
    finally:
        stack.pop()


def open_phase():
    """(name, step) of the innermost phase open on this thread;
    ``("outside", None)`` where none is: the caller's own eager ops."""
    stack = getattr(_open, "stack", None)
    return stack[-1] if stack else (OUTSIDE, None)


def _zero_phase() -> dict:
    return {"count": 0, "total_s": 0.0, "max_s": 0.0, "max_step": -1}


def _add_occurrence(rec: dict, seconds: float, step: int) -> None:
    rec["count"] += 1
    rec["total_s"] += seconds
    if seconds > rec["max_s"]:
        rec["max_s"] = seconds
        rec["max_step"] = step


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
    boundaries: count, total, last, max.  ``drained`` (``fit/drained``,
    kept like a phase): the device with nothing queued, from the return
    of a ``fit/log_sync`` (the newest step's results are on the host) to
    the return of the next ``fit/dispatch``, log_fn, eval and the wait for
    the batch between them included; both ends are reads the two phases
    make anyway, so a boundary costs nothing more and the steps between
    boundaries one comparison."""

    def __init__(self):
        self.step = 0
        self.steps = 0
        self.wall_s = 0.0
        self.phases = {name: _zero_phase() for name in FIT_PHASES}
        self.drained = _zero_phase()
        self.counters: Dict[str, dict] = {}
        self._start = time.perf_counter()
        self._drained_since: Optional[float] = None

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
        with profile_scope(name, "host", args={"step": self.step}), \
                _opened(name, self.step):
            begin = time.perf_counter()
            try:
                yield
            finally:
                end = time.perf_counter()
                _add_occurrence(self.phases[name], end - begin, self.step)
                if name == LOG_SYNC:
                    self._drained_since = end
                elif name == DISPATCH and self._drained_since is not None:
                    _add_occurrence(self.drained, end - self._drained_since,
                                    self.step)
                    self._drained_since = None
                self.wall_s = end - self._start

    def as_dict(self) -> dict:
        out = {"steps": self.steps, "wall_s": self.wall_s,
               "phases": {k: dict(v) for k, v in self.phases.items()},
               DRAINED: dict(self.drained)}
        if self.counters:
            out["counters"] = {k: dict(v) for k, v in self.counters.items()}
        return out


_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
# JAX names a function ``f`` while it traces it and ``jit(f)`` while it
# lowers and compiles it
_WRAPPED = re.compile(r"^(?:jit|pmap)\((.*)\)$")


def _zero_program() -> dict:
    return {"traces": 0, "trace_s": 0.0, "lowers": 0, "lower_s": 0.0,
            "compiles": 0, "backend_s": 0.0, "cache_hits": 0,
            "cache_misses": 0, "retrieval_s": 0.0}


class CompileLog:
    """What each program cost to become runnable, from ``jax.monitoring``:
    the duration events JAX emits with ``fun_name`` when it has traced a
    function to a jaxpr, lowered the jaxpr to a module and got an
    executable for it (XLA's compile, or the persistent cache's read and
    load), and the persistent cache's own events, which carry no name and
    fire inside the named backend interval on the same thread.

    One *occurrence* is one backend event: ``{"t", "fun_name", "phase",
    "step", "trace_s", "lower_s", "backend_s", "cache", "retrieval_s"}``;
    ``t`` on ``time.perf_counter()`` when the executable was there;
    ``phase`` and ``step`` the innermost phase open on that thread
    (:func:`open_phase`); ``trace_s`` and ``lower_s`` what the same thread
    last reported for that function (0 where JAX had the jaxpr already);
    ``cache`` ``"hit"``, ``"miss"`` (compiled and written) or ``"none"``
    (compiled, the cache not asked or the entry under its thresholds).
    A function traced inside another (a nested ``jit``, every ``jnp``
    function) reports its own trace, inside its caller's: such a trace is
    in ``by_fun`` and in no occurrence.  Trace seconds therefore nest;
    lower and backend seconds do not.  Traces are the frequent event
    (thousands a step program), so they are counted on their thread
    without the lock and reach ``by_fun`` at that thread's next lowering
    or compile, or when it calls ``as_dict``.

    Bounded: the first ``keep`` occurrences stay, later ones are counted
    in ``dropped`` and in the sums, and the newest is ``last`` whatever
    its number; ``by_fun`` takes ``keep`` names and adds the rest under
    ``"(other)"``.  The listeners run only when JAX records such an
    event: nothing happens between compiles."""

    def __init__(self, keep: int = 512):
        self.keep = keep
        self._lock = threading.Lock()
        self._pending = threading.local()
        self.clear()

    def clear(self) -> None:
        """Forget everything so far (the listeners stay)."""
        with self._lock:
            self.occurrences = []
            self.last: Optional[dict] = None
            self.dropped = 0
            self.by_fun: Dict[str, dict] = {}
            self.totals = _zero_program()

    @property
    def compiles(self) -> int:
        """Backend events so far: what XLA was asked for, cache hits too."""
        return self.totals["compiles"]

    def snapshot(self) -> dict:
        return {k: self.totals[k]
                for k in ("compiles", "cache_hits", "cache_misses")}

    def install(self) -> "CompileLog":
        """Register with ``jax.monitoring`` (which has no way back: once a
        process, see :func:`compile_log`)."""
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self.on_duration)
        monitoring.register_event_listener(self.on_event)
        return self

    def _program(self, fun_name: str) -> dict:
        rec = self.by_fun.get(fun_name)
        if rec is None:
            if len(self.by_fun) >= self.keep:
                fun_name = "(other)"
            rec = self.by_fun.setdefault(fun_name, _zero_program())
        return rec

    def _thread(self) -> dict:
        state = getattr(self._pending, "state", None)
        if state is None:
            # traced: {fun_name: [traces and seconds not yet in by_fun,
            # the newest trace's seconds]}; lowered: {fun_name: seconds}
            state = self._pending.state = {
                "traced": {}, "lowered": {}, "cache": "none",
                "retrieval_s": 0.0}
        return state

    def _fold(self, traced: dict) -> None:
        """Move a thread's traces into ``by_fun`` (the lock is held)."""
        for fun_name, held in traced.items():
            if held[0]:
                rec = self._program(fun_name)
                rec["traces"] += held[0]
                rec["trace_s"] += held[1]
                self.totals["traces"] += held[0]
                held[0], held[1] = 0, 0.0

    def on_event(self, event: str, **_kw) -> None:
        if event == _CACHE_HIT:
            self._thread()["cache"] = "hit"
        elif event == _CACHE_MISS:
            self._thread()["cache"] = "miss"

    def on_duration(self, event: str, secs: float, fun_name=None,
                    **_kw) -> None:
        if event == _TRACE:
            # the frequent one: a step program's trace reports thousands
            # of nested functions.  Kept on the thread, without the lock;
            # the thread's next lowering or compile folds it into by_fun
            if fun_name is not None:
                traced = self._thread()["traced"]
                held = traced.get(fun_name)
                if held is None:
                    if len(traced) >= self.keep:
                        fun_name = "(other)"
                    held = traced.setdefault(fun_name, [0, 0.0, 0.0])
                held[0] += 1
                held[1] += secs
                held[2] = secs
            return
        if event == _CACHE_RETRIEVAL:
            self._thread()["retrieval_s"] = secs
            return
        if event not in (_LOWER, _BACKEND) or fun_name is None:
            return
        wrapped = _WRAPPED.match(fun_name)
        if wrapped:
            fun_name = wrapped.group(1)
        mine = self._thread()
        traced, lowered = mine["traced"], mine["lowered"]
        with self._lock:
            self._fold(traced)
            rec = self._program(fun_name)
            if event == _LOWER:
                rec["lowers"] += 1
                rec["lower_s"] += secs
                self.totals["lowers"] += 1
                self.totals["lower_s"] += secs
                # held until this function's executable is there; a
                # thread that lowers for ever and compiles nothing keeps
                # the newest few
                if fun_name not in lowered and len(lowered) >= 64:
                    lowered.clear()
                lowered[fun_name] = secs
                return
            # the newest trace of this name, used up by this compile: a
            # program JAX had the jaxpr of reads 0
            held = traced.get(fun_name)
            trace_s = held[2] if held else 0.0
            if held:
                held[2] = 0.0
            lower_s = lowered.pop(fun_name, 0.0)
            cache, retrieval_s = mine["cache"], mine["retrieval_s"]
            mine.update(cache="none", retrieval_s=0.0)
            phase, step = open_phase()
            for sums in (rec, self.totals):
                sums["compiles"] += 1
                sums["backend_s"] += secs
                sums["cache_hits"] += cache == "hit"
                sums["cache_misses"] += cache == "miss"
                sums["retrieval_s"] += retrieval_s
            # nested traces are inside their caller's: the sum over
            # occurrences counts each second once
            self.totals["trace_s"] += trace_s
            self.last = {
                "t": time.perf_counter(), "fun_name": fun_name,
                "phase": phase, "step": step, "trace_s": trace_s,
                "lower_s": lower_s, "backend_s": secs, "cache": cache,
                "retrieval_s": retrieval_s}
            if len(self.occurrences) < self.keep:
                self.occurrences.append(self.last)
            else:
                self.dropped += 1

    def as_dict(self, top: int = 32) -> dict:
        """``by_fun`` cut to the ``top`` names by seconds."""
        def seconds(rec):
            return rec["trace_s"] + rec["lower_s"] + rec["backend_s"]
        with self._lock:
            self._fold(self._thread()["traced"])
            names = sorted(self.by_fun, key=lambda k: -seconds(self.by_fun[k]))
            return {"totals": dict(self.totals), "dropped": self.dropped,
                    "last": dict(self.last) if self.last else None,
                    "functions": len(names),
                    "by_fun": {k: dict(self.by_fun[k]) for k in names[:top]},
                    "occurrences": [dict(o) for o in self.occurrences]}


_compile_log: Optional[CompileLog] = None
_compile_log_lock = threading.Lock()


def compile_log() -> CompileLog:
    """The process's one :class:`CompileLog`, installed at the first call
    (the first ``Trainer`` makes it): programs that ran before it are not
    in it."""
    global _compile_log
    with _compile_log_lock:
        if _compile_log is None:
            _compile_log = CompileLog().install()
        return _compile_log


_MEMORY_FIELDS = ("bytes_in_use", "bytes_reserved", "peak_bytes_in_use")


def fullest_device_stats(devices) -> Optional[dict]:
    """``memory_stats()`` of the device that holds most (in use plus
    reserved); None where the backend keeps none, as the CPU."""
    fullest, most = None, -1
    for device in devices:
        stats = device.memory_stats()
        if not stats:
            continue
        held = stats.get("bytes_in_use", 0) + stats.get("bytes_reserved", 0)
        if held > most:
            fullest, most = stats, held
    return fullest


def state_bytes_per_chip(state, n_devices: int) -> Dict[str, float]:
    """Bytes of the placed state one chip holds, by class, from the
    arrays' global shapes over the mesh's devices."""
    def per_chip(tree):
        return sum(leaf.size * leaf.dtype.itemsize
                   for leaf in jax.tree.leaves(tree)
                   if hasattr(leaf, "size")) / max(1, n_devices)
    return {name: per_chip(getattr(state, name))
            for name in ("params", "opt_state", "sync_state", "model_state")}


class Lifecycle:
    """Always-on record of one trainer's set-up and memory, beside its
    ``LoopStats``.

    ``spans``: for each span of :data:`SETUP_SPANS` that ran, ``begin``
    (``time.perf_counter()`` of its first occurrence), ``seconds`` (the
    first occurrence's), ``total_s`` and ``count``; each is also a
    ``profile_scope`` span with ``step``.  ``marks``: for each name of
    :data:`MEMORY_MARKS` reached, ``count`` and the ``first``, ``last``
    and ``max`` (field by field) of ``{"t", "step", "bytes_in_use",
    "bytes_reserved", "peak_bytes_in_use"}`` on the fullest device; the
    three byte fields are None where ``memory_source`` is None or returns
    None (a backend without allocator statistics).  ``state_bytes``: the
    placed state's bytes a chip by class.  ``programs``: the process's
    :class:`CompileLog`; ``step_fun``: the step function's name in it.

    ``memory_source``: a callable giving the fullest device's
    ``memory_stats()`` dict; the trainer passes its mesh's, tests their
    own."""

    def __init__(self, programs: Optional[CompileLog] = None,
                 memory_source: Optional[Callable[[], Optional[dict]]] = None,
                 step_fun: Optional[str] = None):
        self.programs = programs
        self.memory_source = memory_source
        self.step_fun = step_fun
        self.spans: Dict[str, dict] = {}
        self.marks: Dict[str, dict] = {}
        self.state_bytes: Dict[str, float] = {}
        # what fit's loop tests: the trainer's first step is still to be
        # dispatched; its results are still to reach the host
        self.first_dispatch_due = True
        self.first_boundary_due = False

    @contextlib.contextmanager
    def span(self, name: str, step: int = 0):
        """Time one occurrence of ``name`` and open its span."""
        with profile_scope(name, "host", args={"step": step}), \
                _opened(name, step):
            begin = time.perf_counter()
            try:
                yield
            finally:
                seconds = time.perf_counter() - begin
                rec = self.spans.setdefault(
                    name, {"begin": begin, "seconds": seconds,
                           "total_s": 0.0, "count": 0})
                rec["total_s"] += seconds
                rec["count"] += 1

    def mark(self, name: str, step: int = 0) -> dict:
        """Read the allocator under ``name``."""
        stats = self.memory_source() if self.memory_source else None
        now = {"t": time.perf_counter(), "step": step}
        now.update((k, stats.get(k) if stats else None)
                   for k in _MEMORY_FIELDS)
        rec = self.marks.get(name)
        if rec is None:
            rec = self.marks[name] = {
                "count": 0, "first": now,
                "max": dict.fromkeys(_MEMORY_FIELDS)}
        rec["count"] += 1
        rec["last"] = now
        for k in _MEMORY_FIELDS:
            if now[k] is not None and (rec["max"][k] is None
                                       or now[k] > rec["max"][k]):
                rec["max"][k] = now[k]
        return now

    @contextlib.contextmanager
    def first_dispatch(self, state, n_devices: int, step: int):
        """Around the trainer's first call of its step: the state's bytes
        as the step gets them, the allocator just before, the span."""
        self.first_dispatch_due = False
        self.state_bytes = state_bytes_per_chip(state, n_devices)
        self.mark(FIRST_DISPATCH, step)
        with self.span(FIRST_DISPATCH, step):
            yield
        self.first_boundary_due = True

    def first_boundary(self, step: int) -> None:
        """The first step's results are on the host."""
        self.first_boundary_due = False
        self.mark(FIRST_BOUNDARY, step)

    @property
    def first_boundary_t(self) -> Optional[float]:
        rec = self.marks.get(FIRST_BOUNDARY)
        return rec["first"]["t"] if rec else None

    def step_program(self) -> Optional[dict]:
        """The occurrence in ``programs`` of the step function that fell
        inside this trainer's ``fit/first_dispatch``; None before it, and
        where JAX had the executable already."""
        span = self.spans.get(FIRST_DISPATCH)
        if self.programs is None or span is None:
            return None
        for occ in self.programs.occurrences:
            if occ["fun_name"] == self.step_fun and \
                    0 <= occ["t"] - span["begin"] <= span["seconds"]:
                return occ
        return None

    def step_reserved_bytes(self) -> Optional[int]:
        """Growth of ``bytes_reserved`` from just before the first
        dispatch to the first boundary: the loaded step program's scratch
        space.  None before the boundary or without allocator numbers."""
        before = self.marks.get(FIRST_DISPATCH)
        after = self.marks.get(FIRST_BOUNDARY)
        if not before or not after:
            return None
        lo = before["first"]["bytes_reserved"]
        hi = after["first"]["bytes_reserved"]
        return None if lo is None or hi is None else hi - lo

    def as_dict(self) -> dict:
        out = {"step_fun": self.step_fun,
               "spans": {k: dict(v) for k, v in self.spans.items()},
               "marks": {k: {"count": v["count"], "first": dict(v["first"]),
                             "last": dict(v["last"]), "max": dict(v["max"])}
                         for k, v in self.marks.items()},
               "state_bytes": dict(self.state_bytes),
               "step_reserved_bytes": self.step_reserved_bytes(),
               "first_boundary_t": self.first_boundary_t,
               "step_program": self.step_program()}
        if self.programs is not None:
            out["programs"] = self.programs.as_dict()
        return out


# What the last ``Trainer.fit`` of this process left behind, for a reader
# that no longer holds the trainer (the chip benchmark drops its trainer
# before per-layer metrics are read): its LoopStats, and the abstract
# signature (shapes, dtypes, shardings) of the step's arguments, from
# which a fresh trainer can lower the same program again.
_last = {"loop_stats": None, "step_signature": None, "lifecycle": None}


def record_fit(loop_stats: LoopStats, step_signature,
               lifecycle: Optional[Lifecycle] = None) -> None:
    _last["loop_stats"] = loop_stats
    _last["step_signature"] = step_signature
    _last["lifecycle"] = lifecycle


def last_loop_stats() -> Optional[LoopStats]:
    return _last["loop_stats"]


def last_lifecycle() -> Optional[Lifecycle]:
    """The :class:`Lifecycle` of the trainer whose ``fit`` ran last: the
    one that ran the work, not a trainer built afterwards to lower the
    step again."""
    return _last["lifecycle"]


def last_step_signature():
    """(state, x, y) as ``jax.ShapeDtypeStruct`` trees, or None before the
    first fit."""
    return _last["step_signature"]
