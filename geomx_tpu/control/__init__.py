"""Graft Pilot: the closed-loop WAN controller (docs/control.md).

TSEngine reborn on the telemetry plane (ROADMAP item 3): a
sensor -> policy -> actuator loop that retunes compression ratio,
pipeline depth, and relay topology from LIVE measurements instead of
static env config.

- :mod:`sensors`   — fold links/attribution/probe-registry/resilience
  into one normalized :class:`ControlObservation`;
- :mod:`policy`    — deterministic, hysteresis-guarded policies
  (:class:`RatioPolicy`, :class:`DepthPolicy`, :class:`RelayPolicy`)
  under the :class:`GraftPilot` loop;
- :mod:`actuators` — safe application: ratio changes ride a traced
  scalar operand (no recompile), depth/relay changes go through the
  ``Trainer.apply_control`` recompile boundary, every actuation lands
  in the bounded :class:`DecisionLog` the scheduler serves at
  ``GET /control``.

Gated by ``GEOMX_CONTROL``; the disabled step jaxpr is byte-identical
to a controller-excised build.  Acceptance: tests/test_control.py.
"""

from geomx_tpu.control.actuators import (CONTROL_KEY, ControlActuator,
                                         DecisionLog, control_enabled,
                                         control_operands,
                                         current_ratio_scale,
                                         get_decision_log,
                                         init_control_operands,
                                         reset_decision_log)
from geomx_tpu.control.policy import (Decision, DepthPolicy, GraftPilot,
                                      RatioPolicy, RelayPolicy)
from geomx_tpu.control.sensors import ControlObservation, ControlSensors

__all__ = [
    "CONTROL_KEY", "ControlActuator", "DecisionLog", "control_enabled",
    "control_operands", "current_ratio_scale", "get_decision_log",
    "init_control_operands", "reset_decision_log",
    "Decision", "DepthPolicy", "GraftPilot", "RatioPolicy", "RelayPolicy",
    "ControlObservation", "ControlSensors",
]
