"""Graft Auditor: static analysis over traced/lowered programs.

- ``core``   — jaxpr walker, pass framework, ``Finding``s, severity gate
- ``passes`` — collective-consistency (+ ``audit_cross_party``),
  donation/aliasing, dtype-flow & wire accounting, compressed-path purity
- ``hlo``    — lowered-HLO assertions (fused-vs-unfused matchers)
- ``corpus`` — seeded known-bad programs the auditor must flag

Trace-hygiene linting for the repo's own sources lives in
``tools/graftlint.py`` (AST-level, no jax import).  See docs/analysis.md.
"""

from geomx_tpu.analysis.core import (AuditContext, AuditError, AuditPass,
                                     Finding, audit_enabled,
                                     audit_severity_gate, enforce,
                                     run_passes, summarize, walk_jaxpr)
from geomx_tpu.analysis.passes import (CollectiveConsistencyPass,
                                       DonationPass, DtypeFlowPass,
                                       PurityPass, audit_compressed_path,
                                       audit_cross_party, audit_donation,
                                       audit_dtype_flow,
                                       audit_wire_accounting,
                                       audit_zero_compressed_path,
                                       collective_signature,
                                       diff_collective_signatures)

__all__ = [
    "AuditContext", "AuditError", "AuditPass", "Finding",
    "CollectiveConsistencyPass", "DonationPass", "DtypeFlowPass",
    "PurityPass", "audit_compressed_path", "audit_cross_party",
    "audit_donation", "audit_dtype_flow", "audit_enabled",
    "audit_severity_gate", "audit_wire_accounting",
    "audit_zero_compressed_path",
    "collective_signature", "diff_collective_signatures", "enforce",
    "run_passes", "summarize", "walk_jaxpr",
]
