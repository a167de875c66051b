"""Graft Auditor core: jaxpr walking, the pass framework, and findings.

Three PRs in a row hand-rolled one-off static checks — PR 4's "dense
scatter/cumsum ops are GONE from the lowered HLO" regression, PR 5's
byte-identical-jaxpr telemetry guarantee, a DCE-based collective
counting — because the correctness properties this system lives on are
*program-shape* properties, not runtime ones: every party must execute
the same collective sequence (or the mesh deadlocks/diverges silently,
the failure class "Automatic Cross-Replica Sharding of Weight Update in
Data-Parallel Training" engineers against), the compressed path must
never put a dense payload on the WAN, and disabled subsystems must cost
zero ops.  This package makes those checks a real analysis layer: a
walker over traced jaxprs, passes producing structured ``Finding``s with
equation provenance, and a severity gate (``GEOMX_AUDIT`` /
``GEOMX_AUDIT_SEVERITY``) that turns findings into hard errors at the
recompile boundaries where mismatched programs are born.

Vocabulary:

- :class:`EqnSite`  — one equation plus its nesting path ("shard_map/
  pjit[3]") and index, yielded by :func:`walk_jaxpr`;
- :class:`Finding`  — rule id, severity, message, provenance;
- :class:`AuditPass` — ``run(closed_jaxpr, ctx) -> [Finding]``;
- :func:`run_passes` / :func:`enforce` — drive passes, gate severities.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

# severity order: gate "warning" admits warnings AND errors; "error"
# admits errors only.  "info" findings never raise.
SEVERITIES = ("info", "warning", "error")

# sub-jaxprs of these primitives run on-chip inside one opaque kernel
# launch (Mosaic); their internal equations are not XLA program shape and
# the walker treats the call itself as a leaf op.
OPAQUE_PRIMS = frozenset({"pallas_call"})


def _severity_rank(sev: str) -> int:
    try:
        return SEVERITIES.index(sev)
    except ValueError:
        raise ValueError(
            f"unknown severity {sev!r}: expected one of {SEVERITIES}")


@dataclasses.dataclass(frozen=True)
class Finding:
    """One auditor result, with enough provenance to act on it."""

    rule_id: str                 # e.g. "GX-COLLECTIVE-001"
    severity: str                # "info" | "warning" | "error"
    message: str                 # human-readable, one line
    primitive: str = ""          # offending eqn's primitive name ("" = n/a)
    path: str = ""               # nesting path, e.g. "shard_map/pjit[12]"
    source: str = ""             # jax source_info summary when available
    detail: Optional[dict] = None  # rule-specific structured payload

    def __post_init__(self):
        _severity_rank(self.severity)  # validate eagerly

    def format(self) -> str:
        loc = self.path or "<program>"
        src = f" ({self.source})" if self.source else ""
        return f"[{self.rule_id}:{self.severity}] {loc}{src}: {self.message}"


@dataclasses.dataclass(frozen=True)
class EqnSite:
    """An equation with its provenance inside the (nested) jaxpr."""

    eqn: Any
    path: str     # "/"-joined nesting of enclosing call primitives
    index: int    # flattened walk order (stable across identical traces)

    @property
    def primitive(self) -> str:
        return self.eqn.primitive.name

    def source(self) -> str:
        """Best-effort one-line source provenance for the equation."""
        try:
            frame = self.eqn.source_info.traceback.frames[0]
            return f"{frame.file_name}:{frame.start_line}"
        except Exception:
            return ""


def _sub_jaxprs(eqn) -> Iterator[Any]:
    """Yield every jaxpr nested in an equation's params (pjit/scan jaxpr,
    cond branches, while cond/body, custom_jvp call_jaxpr, ...)."""
    for val in eqn.params.values():
        for sub in (val if isinstance(val, (list, tuple)) else (val,)):
            if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                yield getattr(sub, "jaxpr", sub)


def walk_jaxpr(jaxpr, enter_opaque: bool = False) -> Iterator[EqnSite]:
    """Depth-first walk over every equation of ``jaxpr`` (a Jaxpr or
    ClosedJaxpr), descending into nested jaxprs in deterministic trace
    order.  Equations inside :data:`OPAQUE_PRIMS` bodies (Pallas kernel
    jaxprs) are skipped unless ``enter_opaque`` — a kernel's internals
    are device microcode, not XLA program shape."""
    counter = [0]

    def _walk(core, path):
        core = getattr(core, "jaxpr", core)
        for eqn in core.eqns:
            yield EqnSite(eqn=eqn, path=path, index=counter[0])
            counter[0] += 1
            name = eqn.primitive.name
            if name in OPAQUE_PRIMS and not enter_opaque:
                continue
            sub_path = f"{path}/{name}" if path else name
            for sub in _sub_jaxprs(eqn):
                yield from _walk(sub, sub_path)

    yield from _walk(jaxpr, "")


def aval_bytes(aval) -> int:
    """HBM footprint of a shaped aval (0 for non-array avals)."""
    import numpy as np
    try:
        return int(aval.size) * int(np.dtype(aval.dtype).itemsize)
    except Exception:
        return 0


def aval_sig(aval) -> Tuple[Tuple[int, ...], str]:
    """(shape, dtype) signature of an aval, hashable and repr-stable."""
    try:
        return (tuple(int(d) for d in aval.shape), str(aval.dtype))
    except Exception:
        return ((), "?")


@dataclasses.dataclass
class AuditContext:
    """Per-audit metadata handed to passes.

    ``dense_bytes``: the dense fp32 footprint the compressed-path rules
    compare wire payloads against (largest bucket/leaf).  ``compute_dtype``:
    the declared 16-bit compute dtype for the dtype-flow pass (None
    disables the leak rule).  ``lowered_text``: StableHLO text for passes
    that read lowering-level facts (donation/aliasing).  ``extras`` is a
    free-form bag for rule-specific inputs.
    """

    dense_bytes: Optional[int] = None
    compute_dtype: Optional[str] = None
    lowered_text: Optional[str] = None
    label: str = ""
    extras: Dict[str, Any] = dataclasses.field(default_factory=dict)


class AuditPass:
    """Base class: one named rule family over a traced program."""

    rule_id: str = "GX-BASE-000"
    default_severity: str = "error"

    def run(self, jaxpr, ctx: AuditContext) -> List[Finding]:
        raise NotImplementedError

    def finding(self, message: str, site: Optional[EqnSite] = None,
                severity: Optional[str] = None, rule_id: Optional[str] = None,
                detail: Optional[dict] = None) -> Finding:
        return Finding(
            rule_id=rule_id or self.rule_id,
            severity=severity or self.default_severity,
            message=message,
            primitive=site.primitive if site is not None else "",
            path=(f"{site.path}[{site.index}]" if site is not None else ""),
            source=site.source() if site is not None else "",
            detail=detail)


def run_passes(jaxpr, passes: Sequence[AuditPass],
               ctx: Optional[AuditContext] = None) -> List[Finding]:
    """Run every pass over one traced program; findings concatenate in
    pass order (each pass's findings keep walk order)."""
    ctx = ctx or AuditContext()
    out: List[Finding] = []
    for p in passes:
        out.extend(p.run(jaxpr, ctx))
    return out


class AuditError(Exception):
    """Raised by :func:`enforce` when findings cross the severity gate.
    Carries the full finding list (``.findings``) so callers can log or
    rejudge — the message holds the formatted gate-crossing subset."""

    def __init__(self, findings: Sequence[Finding], gate: str):
        self.findings = list(findings)
        self.gate = gate
        over = [f for f in findings
                if _severity_rank(f.severity) >= _severity_rank(gate)]
        lines = "\n  ".join(f.format() for f in over)
        super().__init__(
            f"graft auditor: {len(over)} finding(s) at or above "
            f"severity {gate!r}:\n  {lines}")


def enforce(findings: Sequence[Finding], gate: str = "error") -> List[Finding]:
    """Raise :class:`AuditError` if any finding's severity reaches
    ``gate``; otherwise return the findings unchanged (callers log the
    sub-gate remainder)."""
    rank = _severity_rank(gate)
    if any(_severity_rank(f.severity) >= rank for f in findings):
        raise AuditError(findings, gate)
    return list(findings)


def summarize(findings: Sequence[Finding]) -> Dict[str, int]:
    """Finding counts per rule id."""
    out: Dict[str, int] = {}
    for f in findings:
        out[f.rule_id] = out.get(f.rule_id, 0) + 1
    return out


# ---------------------------------------------------------------------------
# the audit gate (config surface, mirroring telemetry_enabled)
# ---------------------------------------------------------------------------

def audit_enabled(config: Optional[Any] = None) -> bool:
    """The master auditor gate: ``config.audit`` or ``GEOMX_AUDIT``,
    parsed with the same numeric-boolean rules as every other GEOMX_*
    knob.  Static — read where audit hooks are *built* (Trainer init),
    so flipping it is a rebuild."""
    if config is not None and getattr(config, "audit", False):
        return True
    from geomx_tpu.config import _env_bool
    return _env_bool(["GEOMX_AUDIT"], False)


def audit_severity_gate(config: Optional[Any] = None) -> str:
    """The severity at which findings raise (``GEOMX_AUDIT_SEVERITY`` /
    ``GeoConfig.audit_severity``); below it they only log."""
    gate = None
    if config is not None:
        gate = getattr(config, "audit_severity", None)
    if not gate:
        from geomx_tpu.config import _env
        gate = _env(["GEOMX_AUDIT_SEVERITY"], "error", str)
    _severity_rank(gate)
    return gate
