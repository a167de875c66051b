"""Actuators: apply Graft Pilot decisions safely (docs/control.md).

Three actuation boundaries, by cost:

- **ratio** — the bsc top-k ratio retunes by rewriting a TRACED SCALAR
  OPERAND (``bsc_ratio_scale``) living in ``sync_state["control"]``.
  The compiled step never changes: the configured ratio is the wire
  CAPACITY (static shapes), the scale picks the effective selection
  count below it, and unemitted slots ride the wire as sentinels the
  decompressor already drops.  ``Trainer.apply_control`` swaps the
  operand host-side with a matching sharding, so the jit cache stays at
  one entry (pinned by tests/test_control.py).
- **depth / relay** — pipeline-depth switching is a RECOMPILE boundary
  modeled on ``Trainer.apply_membership`` (per-decision cached step
  programs, error-feedback state carried across the swap, the
  collective-signature audit re-verified before the new program is
  installed); relay re-forming is host-plane only (the scheduler's
  relay chain re-forms from the ``LinkObservatory`` snapshot) and
  touches no device program.

Every actuation lands in the process-global :class:`DecisionLog`
(served by the scheduler's ``GET /control``), the telemetry event log,
and — when a :class:`~geomx_tpu.telemetry.flight.FlightRecorder` is
armed — the flight ring's decision sibling, so anomaly bundles show
the last N actuations alongside the step records.

The trace-time plumbing mirrors ``telemetry.probes``' inline sink: the
traced step opens :func:`control_operands` around its sync calls only
when ``GEOMX_CONTROL`` is on, and :func:`current_ratio_scale` returns
``None`` otherwise — so the disabled step jaxpr is byte-identical to a
controller-excised build (the same hard guarantee the telemetry plane
makes, pinned by ``tests/test_control.py``).
"""

from __future__ import annotations

import collections
import contextlib
import threading
from typing import Any, Dict, List, Optional

CONTROL_KEY = "control"


def control_enabled(config: Optional[Any] = None) -> bool:
    """The master control gate: ``config.control`` or ``GEOMX_CONTROL``
    (same numeric-boolean parse as every GEOMX_* knob).  Static —
    evaluated when the step program is built."""
    if config is not None and getattr(config, "control", False):
        return True
    from geomx_tpu.config import _env_bool
    return _env_bool(["GEOMX_CONTROL"], False)


def init_control_operands():
    """The control-operand subtree ``Trainer.init_state`` threads into
    ``sync_state[CONTROL_KEY]``: the bsc ratio scale starts at 1.0 (the
    configured capacity ratio)."""
    import jax.numpy as jnp
    return {"bsc_ratio_scale": jnp.ones((), jnp.float32)}


# ---------------------------------------------------------------------------
# trace-time operand context (the probes' inline-sink pattern)
# ---------------------------------------------------------------------------

_ctl = threading.local()


@contextlib.contextmanager
def control_operands(ops: Dict[str, Any]):
    """Open the traced control operands for the sync stack: compressors
    deep inside the dc tier read them via :func:`current_ratio_scale`
    without threading a parameter through every signature."""
    prev = getattr(_ctl, "ops", None)
    _ctl.ops = ops
    try:
        yield ops
    finally:
        _ctl.ops = prev


def current_ratio_scale():
    """The traced ``bsc_ratio_scale`` operand, or ``None`` when no
    control context is open (the disabled path — zero ops enter the
    jaxpr)."""
    ops = getattr(_ctl, "ops", None)
    if ops is None:
        return None
    return ops.get("bsc_ratio_scale")


# ---------------------------------------------------------------------------
# decision log (bounded, process-global; the scheduler serves it)
# ---------------------------------------------------------------------------

class DecisionLog:
    """Thread-safe bounded history of applied decisions.  Entries are
    plain JSON-able dicts with NO wall-clock fields — two runs of the
    same seeded scenario must produce byte-identical logs
    (tests/test_control.py)."""

    def __init__(self, capacity: int = 256):
        if capacity <= 0:
            raise ValueError(f"capacity must be > 0 (got {capacity!r})")
        self.capacity = int(capacity)
        self._lock = threading.Lock()
        self._entries: "collections.deque[dict]" = collections.deque(
            maxlen=self.capacity)
        self.total = 0

    def append(self, entry: Dict[str, Any]) -> None:
        with self._lock:
            self._entries.append(dict(entry))
            self.total += 1

    def snapshot(self) -> List[dict]:
        with self._lock:
            return [dict(e) for e in self._entries]

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self.total = 0


_global_log: Optional[DecisionLog] = None
_global_log_lock = threading.Lock()


def get_decision_log() -> DecisionLog:
    global _global_log
    with _global_log_lock:
        if _global_log is None:
            _global_log = DecisionLog()
        return _global_log


def reset_decision_log() -> DecisionLog:
    """Fresh global decision log (test isolation)."""
    global _global_log
    with _global_log_lock:
        _global_log = DecisionLog()
        return _global_log


# ---------------------------------------------------------------------------
# the actuator
# ---------------------------------------------------------------------------

class ControlActuator:
    """Routes decisions to their actuation boundary and records every
    application.

    ``trainer``: the :class:`~geomx_tpu.train.trainer.Trainer` whose
    ``apply_control`` owns the ratio/depth boundaries.  ``relay_apply``:
    optional callable receiving the new relay order (host plane — the
    in-process transports or a WAN model install it; the scheduler's
    decision history records it either way).  ``flight``: optional
    FlightRecorder whose decision ring mirrors the log.
    """

    def __init__(self, trainer=None, relay_apply=None, flight=None,
                 log: Optional[DecisionLog] = None,
                 event_log=None):
        self.trainer = trainer
        self.relay_apply = relay_apply
        self.flight = flight if flight is not None else \
            getattr(trainer, "_flight", None)
        self.log = log if log is not None else get_decision_log()
        self._event_log = event_log

    def apply(self, state, decision):
        """Apply one decision; returns the (possibly new) TrainState.
        Unknown kinds raise — a controller emitting a decision no
        actuator understands is a bug, not a log line."""
        kind = getattr(decision, "kind", None)
        if kind in ("ratio", "depth"):
            if self.trainer is None:
                raise ValueError(
                    f"{kind!r} decision needs a trainer-bound actuator "
                    "(ControlActuator(trainer=...))")
            state = self.trainer.apply_control(state, decision)
        elif kind == "relay":
            if self.relay_apply is not None:
                self.relay_apply(list(decision.value))
        else:
            raise ValueError(f"unknown decision kind {kind!r}; "
                             "expected ratio | depth | relay")
        self._record(decision)
        return state

    def _record(self, decision) -> None:
        entry = decision.to_json()
        self.log.append(entry)
        if self.flight is not None:
            self.flight.record_decision(entry)
        from geomx_tpu.telemetry import get_registry, log_event
        reg = get_registry()
        reg.counter("geomx_control_decisions_total",
                    "Controller actuations applied",
                    ("kind",)).labels(kind=entry["kind"]).inc()
        if entry["kind"] == "ratio":
            reg.gauge("geomx_control_ratio",
                      "Current controller-set bsc ratio").set(
                float(entry["value"]))
        elif entry["kind"] == "depth":
            reg.gauge("geomx_control_pipeline_depth",
                      "Current controller-set pipeline depth").set(
                float(entry["value"]))
        # the event kind is positional; the decision's own "kind" field
        # rides as decision_kind so the two never collide
        ev = {("decision_kind" if k == "kind" else k): v
              for k, v in entry.items()}
        if self._event_log is not None:
            self._event_log.emit("control_decision", **ev)
        else:
            log_event("control_decision", **ev)
