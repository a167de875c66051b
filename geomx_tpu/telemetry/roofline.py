"""Roofline & MFU accounting from the compiled step program.

ROADMAP item 5 says MFU sits at ~0.17 and "the compute side, not the
wire, now bounds single-chip speed" — this module makes that kind of
claim *derivable from a running program* instead of a one-off:

- :func:`compiled_costs` reads model FLOPs and HBM bytes-accessed per
  step from XLA's ``compiled.cost_analysis()``;
- :func:`roofline_record` grades the measured step time against the
  three rooflines that can bound it — peak compute, memory bandwidth,
  and the WAN wire (bytes from ``sync.wire_accounting``) — and emits a
  verdict naming the binding resource, in the wire/compute-balance
  spirit of EQuARX (PAPERS.md);
- :func:`publish_roofline` exports the numbers as registry gauges so
  the scheduler's ``/metrics`` surface serves live MFU.

The verdict is the sensor the self-tuning controller (ROADMAP item 3)
and the MFU-raising work (item 5) both consume: "wire_bound" means
compression/pipelining has headroom to buy, "compute_bound" means it
does not and the kernels are the lever.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

# Published per-chip peaks, keyed by the EXACT ``device_kind`` JAX reports
# (``jax.devices()[0].device_kind``).  The one table the roofline
# records read; a device that is not in it is an error, never a
# default or a calibration — add its row, with its source, to use it.
#
# "TPU v5 lite" is the v5e.  Source: Google Cloud documentation, "TPU
# v5e" system architecture — 197 TFLOP/s bf16 and 819 GB/s of HBM
# bandwidth per chip.
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}


def device_peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of ``device_kind``; raises ``ValueError`` for
    a device the table does not list."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r}: "
            f"telemetry/roofline.DEVICE_PEAKS lists {sorted(DEVICE_PEAKS)}; "
            "a roofline or MFU needs the device's own row") from None


def compiled_costs(compiled) -> Dict[str, Any]:
    """FLOPs and bytes-accessed per execution from a compiled program's
    ``cost_analysis()``; ``{"available": False}`` where the backend
    offers none (some CPU jaxlibs)."""
    try:
        ca = compiled.cost_analysis()
    except Exception as e:
        return {"available": False, "error": repr(e)}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not ca:
        return {"available": False}
    out: Dict[str, Any] = {"available": True}
    flops = float(ca.get("flops", 0.0) or 0.0)
    out["flops"] = flops if flops > 0 else None
    byt = float(ca.get("bytes accessed", 0.0) or 0.0)
    out["bytes_accessed"] = byt if byt > 0 else None
    return out


def roofline_record(*, flops: Optional[float],
                    step_time_s: float,
                    peak_flops_per_s: Optional[float],
                    hbm_bytes: Optional[float] = None,
                    hbm_bytes_per_s: Optional[float] = None,
                    wire_bytes: Optional[float] = None,
                    wire_bytes_per_s: Optional[float] = None
                    ) -> Dict[str, Any]:
    """Grade one step against the three rooflines.

    Per-resource lower-bound times are ``t_compute = flops/peak``,
    ``t_memory = hbm_bytes/hbm_bw``, ``t_wire = wire_bytes/wire_bw``
    (each only when both numerator and rate are known); the verdict
    names the largest — the resource whose roofline the measured step
    cannot beat.  ``mfu`` is achieved FLOP/s over peak,
    ``arithmetic_intensity`` is FLOPs per HBM byte, and
    ``ridge_flops_per_byte`` (peak/bw) locates the measured intensity
    on the classic roofline: below the ridge the memory roof is the
    binding one at full utilization.
    """
    if step_time_s <= 0:
        raise ValueError(f"step_time_s must be > 0 (got {step_time_s!r})")
    rec: Dict[str, Any] = {
        "flops_per_step": flops, "step_time_s": step_time_s,
        "peak_flops_per_s": peak_flops_per_s,
        "hbm_bytes_per_step": hbm_bytes,
        "wire_bytes_per_step": wire_bytes,
    }
    achieved = (flops / step_time_s) if flops else None
    rec["achieved_flops_per_s"] = achieved
    rec["mfu"] = (achieved / peak_flops_per_s
                  if achieved and peak_flops_per_s else None)
    rec["arithmetic_intensity"] = (flops / hbm_bytes
                                   if flops and hbm_bytes else None)
    rec["ridge_flops_per_byte"] = (
        peak_flops_per_s / hbm_bytes_per_s
        if peak_flops_per_s and hbm_bytes_per_s else None)

    bounds: Dict[str, float] = {}
    if flops and peak_flops_per_s:
        bounds["compute"] = flops / peak_flops_per_s
    if hbm_bytes and hbm_bytes_per_s:
        bounds["memory"] = hbm_bytes / hbm_bytes_per_s
    if wire_bytes and wire_bytes_per_s:
        bounds["wire"] = wire_bytes / wire_bytes_per_s
    rec["bound_times_s"] = bounds
    if bounds:
        verdict = max(bounds, key=lambda k: bounds[k])
        rec["bound"] = f"{verdict}_bound"
        ordered = sorted(bounds.values(), reverse=True)
        # dominance of the verdict over the runner-up: 1.0 = ties, big =
        # unambiguous.  With one resource known there is no runner-up.
        rec["bound_dominance"] = (ordered[0] / ordered[1]
                                  if len(ordered) > 1 and ordered[1] > 0
                                  else None)
        # fraction of the measured step the binding resource explains —
        # <1 always (the roofline is a lower bound); near 1 means the
        # step runs at that roofline, small means overhead elsewhere
        rec["bound_explains_fraction"] = min(
            bounds[verdict] / step_time_s, 1.0)
    else:
        rec["bound"] = "unknown"
        rec["bound_dominance"] = None
        rec["bound_explains_fraction"] = None
    return rec


def publish_roofline(rec: Dict[str, Any], registry=None) -> None:
    """Export a roofline record as registry gauges: ``geomx_mfu``,
    ``geomx_arithmetic_intensity``, ``geomx_roofline_bound{bound=...}``
    (one-hot over the three verdicts) and the per-resource lower-bound
    times ``geomx_roofline_bound_seconds{resource=...}``."""
    from geomx_tpu.telemetry.registry import get_registry
    reg = registry if registry is not None else get_registry()
    if rec.get("mfu") is not None:
        reg.gauge("geomx_mfu",
                  "Model FLOPs utilization of the measured step").set(
            float(rec["mfu"]))
    if rec.get("arithmetic_intensity") is not None:
        reg.gauge("geomx_arithmetic_intensity",
                  "Step FLOPs per HBM byte accessed").set(
            float(rec["arithmetic_intensity"]))
    fam = reg.gauge("geomx_roofline_bound",
                    "1 on the resource verdict bounding the step",
                    ("bound",))
    for b in ("compute_bound", "memory_bound", "wire_bound"):
        fam.labels(bound=b).set(1.0 if rec.get("bound") == b else 0.0)
    fam_t = reg.gauge("geomx_roofline_bound_seconds",
                      "Per-resource roofline lower bound on step time",
                      ("resource",))
    for res, t in (rec.get("bound_times_s") or {}).items():
        fam_t.labels(resource=res).set(float(t))
