"""Unified telemetry plane (docs/telemetry.md).

Sensors:

- :mod:`registry` — process-global Counter/Gauge/Histogram registry
  every subsystem writes into;
- :mod:`probes` — in-graph step-health probes (grad norm, NaN/Inf,
  achieved compression, EF residuals), gated by ``GEOMX_TELEMETRY``
  with a jaxpr-identical disabled path;
- :mod:`tracing` — cross-party WAN round correlation (``round_id``
  spans + :func:`merge_traces`);
- :mod:`export` — Prometheus text exposition and the bounded JSONL
  event log.

Interpretation (the step-time observatory, built on the sensors):

- :mod:`attribution` — Chrome traces -> per-step compute / hidden-comms
  / exposed-comms / host-stall phase breakdown;
- :mod:`roofline` — MFU, arithmetic intensity and a compute/memory/
  wire bound verdict from ``compiled.cost_analysis()`` + wire
  accounting;
- :mod:`links` — per-(party, peer) EWMA throughput/RTT/loss estimates
  from replayed WAN round spans (:class:`LinkObservatory`);
- :mod:`flight` — bounded per-step flight recorder with deterministic
  anomaly rules and forensics bundles (``GEOMX_FLIGHT``).

Whole-run capture (built on all of the above):

- :mod:`capsule` — run capsules: one versioned archive of the whole
  observability state with bit-exact offline replay
  (``GEOMX_CAPSULE``, ``tools/runcap.py``);
- :mod:`costmodel` — a step-time cost model fitted from capsule
  records for offline what-if search over candidate configs.
"""

from geomx_tpu.telemetry.attribution import (attribute_merged,
                                             attribute_trace,
                                             classify_span,
                                             publish_attribution)
from geomx_tpu.telemetry.capsule import (Capsule, RegistrySampler,
                                         RunCapsule, capsule_enabled,
                                         capsule_from_config,
                                         sample_registry)
from geomx_tpu.telemetry.costmodel import (StepTimeCostModel,
                                           candidate_wire_bytes,
                                           fit_affine_link)
from geomx_tpu.telemetry.export import (EventLog, get_event_log, log_event,
                                        parse_prometheus_text,
                                        render_prometheus)
from geomx_tpu.telemetry.flight import (FlightRecorder, flight_enabled,
                                        flight_recorder_from_config,
                                        install_incident_recorder,
                                        notify_host_incident,
                                        uninstall_incident_recorder)
from geomx_tpu.telemetry.ledger import (RoundLedger, get_round_ledger,
                                        reset_round_ledger)
from geomx_tpu.telemetry.links import (LinkObservatory,
                                       get_link_observatory,
                                       reset_link_observatory)
from geomx_tpu.telemetry.probes import telemetry_enabled
from geomx_tpu.telemetry.registry import (MetricRegistry, get_registry,
                                          reset_registry)
from geomx_tpu.telemetry.roofline import publish_roofline, roofline_record
from geomx_tpu.telemetry.tracing import merge_traces, rounds_in_trace

__all__ = [
    "MetricRegistry", "get_registry", "reset_registry",
    "telemetry_enabled",
    "EventLog", "get_event_log", "log_event",
    "render_prometheus", "parse_prometheus_text",
    "merge_traces", "rounds_in_trace",
    "attribute_trace", "attribute_merged", "classify_span",
    "publish_attribution",
    "roofline_record", "publish_roofline",
    "LinkObservatory", "get_link_observatory", "reset_link_observatory",
    "RoundLedger", "get_round_ledger", "reset_round_ledger",
    "FlightRecorder", "flight_enabled", "flight_recorder_from_config",
    "notify_host_incident", "install_incident_recorder",
    "uninstall_incident_recorder",
    "RunCapsule", "Capsule", "RegistrySampler", "sample_registry",
    "capsule_enabled", "capsule_from_config",
    "StepTimeCostModel", "fit_affine_link", "candidate_wire_bytes",
]
