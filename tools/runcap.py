#!/usr/bin/env python3
"""runcap: inspect, diff and explain run capsules.

A run capsule (``geomx_tpu/telemetry/capsule.py``, docs/telemetry.md
"Run capsules") is one versioned archive holding a training run's
whole observability state.  This tool is the operator's read side:

- ``info <cap>``           — manifest + section accounting;
- ``snapshot <cap>``       — the offline-replayed per-link
  LinkObservatory snapshot (bit-identical to the live one; imports
  geomx_tpu for the real replay fold);
- ``diff <a> <b>``         — structured numeric diff of two capsules'
  summaries (phases, links, probes, honesty);
- ``explain <a> <b>``      — the ranked "what moved" findings: the
  degraded link, the phase fraction that grew, the probe or honesty
  ratio that drifted — what a tripped perf gate should NAME instead
  of just flipping red.

``diff``/``explain``/``info`` are pure stdlib readers over the
capsule's pre-computed ``summary`` section (importable without jax or
the repo); only ``snapshot`` re-runs the real replay fold.

Exit status: 0 on success, 2 on usage / unreadable-capsule errors.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, List, Optional

# findings below these floors are noise, not explanations
PHASE_FLOOR = 0.05      # absolute phase-fraction move
REL_FLOOR = 0.10        # relative move for links / probes
HONESTY_FLOOR = 0.05    # relative honesty-ratio move


def load_doc(path: str) -> dict:
    with open(path) as f:
        doc = json.load(f)
    manifest = doc.get("manifest") or {}
    if manifest.get("kind") != "geomx_run_capsule":
        raise ValueError(f"{path}: not a run capsule "
                         f"(kind={manifest.get('kind')!r})")
    return doc


def _summary(doc: dict) -> dict:
    return doc.get("summary") or {}


def _rel(a: Optional[float], b: Optional[float]) -> Optional[float]:
    if a is None or b is None:
        return None
    if a == 0:
        return None if b == 0 else float("inf")
    return (b - a) / abs(a)


# ---------------------------------------------------------------------------
# diff / explain (pure functions over two capsule docs)
# ---------------------------------------------------------------------------

def diff_docs(a: dict, b: dict) -> dict:
    """Structured numeric diff of two capsules' summary sections."""
    sa, sb = _summary(a), _summary(b)
    out: Dict[str, Any] = {"a_steps": sa.get("num_steps"),
                           "b_steps": sb.get("num_steps")}
    phases: Dict[str, dict] = {}
    for name in sorted(set(sa.get("phase_means", {}))
                       | set(sb.get("phase_means", {}))):
        va = sa.get("phase_means", {}).get(name)
        vb = sb.get("phase_means", {}).get(name)
        phases[name] = {"a": va, "b": vb,
                        "delta": None if va is None or vb is None
                        else vb - va}
    out["phases"] = phases
    links: Dict[str, dict] = {}
    for link in sorted(set(sa.get("links", {}))
                       | set(sb.get("links", {}))):
        la = sa.get("links", {}).get(link) or {}
        lb = sb.get("links", {}).get(link) or {}
        entry = {}
        for metric in ("throughput_bps", "rtt_s", "loss_rate"):
            va, vb = la.get(metric), lb.get(metric)
            entry[metric] = {"a": va, "b": vb, "rel": _rel(va, vb)}
        links[link] = entry
    out["links"] = links
    probes: Dict[str, dict] = {}
    for name in sorted(set(sa.get("probe_medians", {}))
                       | set(sb.get("probe_medians", {}))):
        va = sa.get("probe_medians", {}).get(name)
        vb = sb.get("probe_medians", {}).get(name)
        probes[name] = {"a": va, "b": vb, "rel": _rel(va, vb)}
    out["probes"] = probes
    ha, hb = sa.get("wire_honesty_ratio"), sb.get("wire_honesty_ratio")
    if ha is not None or hb is not None:
        out["wire_honesty_ratio"] = {"a": ha, "b": hb,
                                     "rel": _rel(ha, hb)}
    return out


def explain_docs(a: dict, b: dict, top: int = 8) -> List[dict]:
    """Ranked findings naming what moved between capsule ``a`` (the
    reference run) and ``b`` (the suspect run), most significant
    first.  Each finding carries a machine section (kind/name/metric/
    values) and a human ``text``."""
    d = diff_docs(a, b)
    findings: List[dict] = []
    for name, v in d["phases"].items():
        if v["delta"] is None or abs(v["delta"]) < PHASE_FLOOR:
            continue
        findings.append({
            "kind": "phase", "name": name, "metric": "fraction",
            "a": v["a"], "b": v["b"], "score": abs(v["delta"]) * 4,
            "text": (f"phase {name} moved "
                     f"{v['a']:.3f} -> {v['b']:.3f} "
                     f"({v['delta']:+.3f} of the step)")})
    for link, metrics in d["links"].items():
        for metric, v in metrics.items():
            rel = v["rel"]
            if rel is None or abs(rel) < REL_FLOOR:
                continue
            # a throughput DROP and an rtt/loss RISE are the degraded
            # directions; score them by magnitude either way
            findings.append({
                "kind": "link", "name": link, "metric": metric,
                "a": v["a"], "b": v["b"], "score": abs(rel),
                "text": (f"link {link} {metric} "
                         f"{v['a']:.4g} -> {v['b']:.4g} "
                         f"({rel:+.0%})")})
    for name, v in d["probes"].items():
        rel = v["rel"]
        if rel is None or abs(rel) < REL_FLOOR:
            continue
        findings.append({
            "kind": "probe", "name": name, "metric": "median",
            "a": v["a"], "b": v["b"], "score": abs(rel) * 0.5,
            "text": (f"probe {name} median {v['a']:.4g} -> "
                     f"{v['b']:.4g} ({rel:+.0%})")})
    h = d.get("wire_honesty_ratio")
    if h and h.get("rel") is not None \
            and abs(h["rel"]) >= HONESTY_FLOOR:
        findings.append({
            "kind": "honesty", "name": "wire_honesty_ratio",
            "metric": "mean", "a": h["a"], "b": h["b"],
            "score": abs(h["rel"]) * 2,
            "text": (f"wire honesty ratio {h['a']:.4g} -> "
                     f"{h['b']:.4g} ({h['rel']:+.0%}) — measured "
                     "bytes drifted against declared")})
    findings.sort(key=lambda f: -f["score"])
    return findings[:top]


def info_doc(doc: dict) -> dict:
    m = doc.get("manifest") or {}
    return {
        "kind": m.get("kind"), "version": m.get("version"),
        "created_unix": m.get("created_unix"),
        "written_unix": m.get("written_unix"),
        "chaos_schedule": m.get("chaos_schedule"),
        "sample_s": m.get("sample_s"),
        "build": m.get("build"),
        "num_steps": len(doc.get("steps") or []),
        "num_link_observations": len(doc.get("link_journal") or []),
        "num_registry_samples": len(doc.get("registry_samples") or []),
        "num_traces": len(doc.get("traces") or []),
        "num_ledger_records":
            len((doc.get("ledger") or {}).get("records") or []),
        "num_events": len(doc.get("events") or []),
        "num_decisions": len(doc.get("decisions") or []),
        "dropped": {k: m.get(k, 0) for k in
                    ("steps_dropped", "journal_dropped",
                     "samples_dropped")},
    }


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="runcap",
        description="Inspect, diff and explain run capsules.")
    sub = ap.add_subparsers(dest="cmd", required=True)
    p = sub.add_parser("info", help="manifest + section accounting")
    p.add_argument("capsule")
    p = sub.add_parser("snapshot",
                       help="offline-replayed per-link snapshot")
    p.add_argument("capsule")
    p.add_argument("--now", type=float, default=None,
                   help="replay instant (default: end of journal)")
    p = sub.add_parser("diff", help="structured diff of two capsules")
    p.add_argument("a")
    p.add_argument("b")
    p = sub.add_parser("explain",
                       help="ranked findings: what moved a -> b")
    p.add_argument("a")
    p.add_argument("b")
    p.add_argument("--top", type=int, default=8)
    args = ap.parse_args(argv)

    try:
        if args.cmd == "info":
            print(json.dumps(info_doc(load_doc(args.capsule)),
                             sort_keys=True))
        elif args.cmd == "snapshot":
            # the one geomx-importing path: the REAL replay fold.
            # Running from a checkout (tools/ on sys.path, repo not
            # pip-installed) still works via the parent-dir fallback.
            try:
                from geomx_tpu.telemetry.capsule import Capsule
            except ModuleNotFoundError:
                sys.path.insert(0, os.path.dirname(
                    os.path.dirname(os.path.abspath(__file__))))
                from geomx_tpu.telemetry.capsule import Capsule
            cap = Capsule.load(args.capsule)
            print(json.dumps(cap.link_snapshot(now=args.now),
                             sort_keys=True))
        elif args.cmd == "diff":
            print(json.dumps(
                diff_docs(load_doc(args.a), load_doc(args.b)),
                sort_keys=True))
        elif args.cmd == "explain":
            findings = explain_docs(load_doc(args.a),
                                    load_doc(args.b), top=args.top)
            for f in findings:
                print(f"[{f['kind']}] {f['text']}")
            if not findings:
                print("no significant movement between capsules")
    except (OSError, ValueError) as e:
        print(f"runcap: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
