"""Step-time observatory (ISSUE 8; geomx_tpu/telemetry/ interpretation
layer, docs/telemetry.md).

The contracts under test:

- attribution: classification of the repo's recorded span names, the
  interval algebra that partitions a step window into four DISJOINT
  phases summing to the window exactly, and the depth-1 pipeline case
  where the same comm spans flip from exposed to hidden;
- roofline: MFU / arithmetic-intensity / bound-verdict math on pinned
  cost_analysis fixtures, plus gauge publication;
- links: EWMA convergence, staleness decay, deterministic replay of
  chaos-schedule rounds, and reproduction of an injected per-link
  bandwidth asymmetry;
- flight recorder: bounded ring semantics, each anomaly rule on
  crafted histories, deterministic auto-dump naming the poisoned
  party, and the trainer wiring (warn when riding without probes);
- satellites: profiler dump span/dropped accounting, event-log
  rotation counter, scheduler /healthz + build-info gauge.
"""

import json
import math
import os
import urllib.request

import numpy as np
import optax
import pytest

from geomx_tpu.config import GeoConfig
from geomx_tpu.models import MLP
from geomx_tpu.service.scheduler import GeoScheduler, SchedulerClient
from geomx_tpu.sync import get_sync_algorithm
from geomx_tpu.telemetry import parse_prometheus_text
from geomx_tpu.telemetry.attribution import (PHASES, attribute_merged,
                                             attribute_trace,
                                             attribute_window,
                                             classify_span,
                                             publish_attribution)
from geomx_tpu.telemetry.flight import (DENSITY_DRIFT, EXPOSED_JUMP,
                                        GRAD_SPIKE, NONFINITE,
                                        FlightRecorder,
                                        flight_recorder_from_config)
from geomx_tpu.telemetry.links import LinkObservatory
from geomx_tpu.telemetry.registry import MetricRegistry
from geomx_tpu.telemetry.roofline import (compiled_costs, device_peaks,
                                          publish_roofline,
                                          roofline_record)
from geomx_tpu.topology import HiPSTopology
from geomx_tpu.train import Trainer
from geomx_tpu.utils.profiler import Profiler


def _span(name, cat, ts, dur, pid=1, tid=1, args=None):
    ev = {"name": name, "cat": cat, "ph": "X", "ts": float(ts),
          "dur": float(dur), "pid": pid, "tid": tid}
    if args:
        ev["args"] = args
    return ev


# --------------------------------------------------------------------------
# attribution: classification + interval algebra
# --------------------------------------------------------------------------

def test_classify_span_rule_table():
    assert classify_span("train/step") == "step"
    assert classify_span("train/compute") == "compute"
    # the repo's kernel spans classify by category
    assert classify_span("bsc/select_pack", "kernel") == "compute"
    assert classify_span("anything", "compute") == "compute"
    # comm by category (dc_pipeline/launch, bucket collectives)
    assert classify_span("dc_pipeline/launch", "comm") == "comms"
    # host-plane WAN spans classify by name even with no category
    assert classify_span("RelayToGlobal:w") == "comms"
    assert classify_span("RelayRowSparse:emb") == "comms"
    assert classify_span("ServerPush:w") == "comms"
    assert classify_span("dc_allreduce/bucket0") == "comms"
    assert classify_span("dc_pipeline/apply") == "comms"
    # unmatched spans attribute to nothing (their time is host_stall)
    assert classify_span("Heartbeat", "host") is None
    assert classify_span("thread_name", "") is None


def test_attribute_window_exact_phase_math():
    """Known durations: window [0, 100); compute [0, 60); comms
    [40, 90).  Hidden = [40, 60) = 20, compute-only = 40, exposed =
    [60, 90) = 30, stall = 10 — and the four sum to the window."""
    rec = attribute_window((0.0, 100.0), [(0.0, 60.0)], [(40.0, 90.0)])
    assert rec["compute"] == pytest.approx(40.0)
    assert rec["hidden_comms"] == pytest.approx(20.0)
    assert rec["exposed_comms"] == pytest.approx(30.0)
    assert rec["host_stall"] == pytest.approx(10.0)
    assert sum(rec[p] for p in PHASES) == pytest.approx(rec["total"])
    # spans outside the window are clipped, overlapping spans merged
    rec = attribute_window((10.0, 20.0),
                           [(0.0, 12.0), (11.0, 14.0)], [(19.0, 99.0)])
    assert rec["compute"] == pytest.approx(4.0)
    assert rec["exposed_comms"] == pytest.approx(1.0)
    assert rec["host_stall"] == pytest.approx(5.0)


def test_attribute_trace_synthetic_known_phases():
    """Three steps with pinned durations; the summary fractions must
    sum to ~1.0 and match the hand-computed per-phase totals."""
    events = []
    for i in range(3):
        t = i * 100.0
        events.append(_span("train/step", "step", t, 100.0,
                            args={"step": i}))
        events.append(_span("train/compute", "compute", t, 60.0))
        # comm half-hidden under compute: [40, 90) within each step
        events.append(_span("dc_allreduce/bucket0", "comm", t + 40.0,
                            50.0, tid=2))
    doc = {"traceEvents": events}
    att = attribute_trace(doc)
    assert att["num_steps"] == 3
    for s in att["steps"]:
        assert s["compute"] == pytest.approx(40.0)
        assert s["hidden_comms"] == pytest.approx(20.0)
        assert s["exposed_comms"] == pytest.approx(30.0)
        assert s["host_stall"] == pytest.approx(10.0)
    assert sum(att["summary"].values()) == pytest.approx(1.0)
    assert att["summary"]["exposed_comms"] == pytest.approx(0.30)
    assert [s["step"] for s in att["steps"]] == [0, 1, 2]


def test_attribute_trace_intergap_is_host_stall():
    """extend_to_next: the gap between consecutive step spans (input
    pipeline, host loop) lands in host_stall instead of vanishing."""
    events = [
        _span("train/step", "step", 0.0, 80.0, args={"step": 0}),
        _span("train/compute", "compute", 0.0, 80.0),
        _span("train/step", "step", 100.0, 80.0, args={"step": 1}),
        _span("train/compute", "compute", 100.0, 80.0),
    ]
    att = attribute_trace({"traceEvents": events})
    # step 0's window extends to step 1's start: 80 compute + 20 stall
    assert att["steps"][0]["host_stall"] == pytest.approx(20.0)
    att_raw = attribute_trace({"traceEvents": events},
                              extend_to_next=False)
    assert att_raw["steps"][0]["host_stall"] == pytest.approx(0.0)


def _modeled_trace(compute_us, dcn_us, comm_on_weight_path):
    """A Chrome-trace timeline of steps of the given compute durations
    with a DCN delay placed by the step's dependency structure:

    - collective ON the weight path (synchronous): the step blocks on
      the wire, so the comm span follows compute serially inside the
      step window;
    - collective OFF the weight path (pipelined): the collective
      launched as step t's gradients land completes under step t+1's
      compute, so the comm span overlaps the next window."""
    events = []
    t = 0.0
    inflight_end = 0.0
    for i, c in enumerate(compute_us):
        comm_start = t + c  # launch when the grads are ready
        if comm_on_weight_path:
            step_dur = c + dcn_us
        else:
            step_dur = max(c, inflight_end - t)
            inflight_end = comm_start + dcn_us
        events.append(_span("train/step", "step", t, step_dur,
                            args={"step": i}))
        events.append(_span("train/compute", "compute", t, c))
        events.append(_span("dc_allreduce/injected" if comm_on_weight_path
                            else "dc_pipeline/launch", "comm", comm_start,
                            dcn_us, tid=2))
        t += step_dur
    return {"traceEvents": events}


def test_exposed_comms_drop_under_pipeline_depth_1():
    """THE acceptance case: identical compute + DCN delay, but the
    pipelined timeline launches each collective to land under the NEXT
    step's compute — the exposed fraction must drop (to zero when
    compute covers the delay)."""
    compute_us = [50_000.0] * 6
    dcn_us = 30_000.0
    att_sync = attribute_trace(_modeled_trace(
        compute_us, dcn_us, comm_on_weight_path=True))
    att_pipe = attribute_trace(_modeled_trace(
        compute_us, dcn_us, comm_on_weight_path=False))
    assert sum(att_sync["summary"].values()) == pytest.approx(1.0)
    assert sum(att_pipe["summary"].values()) == pytest.approx(1.0)
    # sync: every delay microsecond is exposed (30/80 of the step)
    assert att_sync["summary"]["exposed_comms"] == pytest.approx(
        30.0 / 80.0, rel=1e-3)
    # pipelined with compute > delay: the wire fully hides
    assert att_pipe["summary"]["exposed_comms"] == pytest.approx(
        0.0, abs=1e-6)
    assert att_pipe["summary"]["hidden_comms"] > 0.0
    # delay larger than compute: overlap is partial but still a strict
    # improvement over the synchronous timeline
    att_sync2 = attribute_trace(_modeled_trace(
        compute_us, 80_000.0, comm_on_weight_path=True))
    att_pipe2 = attribute_trace(_modeled_trace(
        compute_us, 80_000.0, comm_on_weight_path=False))
    assert (att_pipe2["summary"]["exposed_comms"]
            < att_sync2["summary"]["exposed_comms"])


def test_attribute_merged_per_party_rows():
    """Two parties' dumps merged on the wall-clock anchor: each party's
    process row attributes separately under its own label."""
    docs = []
    for rank in range(2):
        events = [
            _span("train/step", "step", 0.0, 100.0, pid=os.getpid(),
                  args={"step": 0}),
            _span("train/compute", "compute", 0.0, 70.0,
                  pid=os.getpid()),
        ]
        docs.append({"traceEvents": events, "displayTimeUnit": "ms",
                     "metadata": {"anchor_unix_us": 1e15 + rank,
                                  "rank": rank}})
    out = attribute_merged(docs, labels=["party0", "party1"])
    assert set(out["parties"]) == {"party0", "party1"}
    for att in out["parties"].values():
        assert att["num_steps"] == 1
        assert sum(att["summary"].values()) == pytest.approx(1.0)


def test_publish_attribution_gauges():
    reg = MetricRegistry()
    publish_attribution({"compute": 0.7, "hidden_comms": 0.1,
                         "exposed_comms": 0.15, "host_stall": 0.05},
                        registry=reg)
    fam = reg.get("geomx_phase_fraction")
    assert fam.labels(phase="exposed_comms").value == pytest.approx(0.15)
    assert sum(fam.labels(phase=p).value for p in PHASES) == \
        pytest.approx(1.0)


# --------------------------------------------------------------------------
# roofline: verdict math on pinned fixtures
# --------------------------------------------------------------------------

def test_roofline_verdict_math_pinned():
    """Pinned cost_analysis numbers; each resource made binding in
    turn, with MFU / intensity / dominance hand-checked."""
    # compute-bound: t_compute 0.5 ms >> t_memory 0.1 ms, no wire
    rec = roofline_record(flops=1e9, step_time_s=1e-3,
                          peak_flops_per_s=2e12,
                          hbm_bytes=1e8, hbm_bytes_per_s=1e12)
    assert rec["bound"] == "compute_bound"
    assert rec["mfu"] == pytest.approx(0.5)          # 1e12 / 2e12
    assert rec["arithmetic_intensity"] == pytest.approx(10.0)
    assert rec["ridge_flops_per_byte"] == pytest.approx(2.0)
    assert rec["bound_times_s"]["compute"] == pytest.approx(5e-4)
    assert rec["bound_dominance"] == pytest.approx(5.0)
    assert rec["bound_explains_fraction"] == pytest.approx(0.5)

    # memory-bound: bytes dominate (intensity below the ridge)
    rec = roofline_record(flops=1e8, step_time_s=1e-3,
                          peak_flops_per_s=2e12,
                          hbm_bytes=8e8, hbm_bytes_per_s=1e12)
    assert rec["bound"] == "memory_bound"
    assert rec["arithmetic_intensity"] < rec["ridge_flops_per_byte"]

    # wire-bound: a slow WAN link out-bounds both chip roofs
    rec = roofline_record(flops=1e9, step_time_s=0.2,
                          peak_flops_per_s=2e12,
                          hbm_bytes=1e8, hbm_bytes_per_s=1e12,
                          wire_bytes=1.25e6, wire_bytes_per_s=1.25e7)
    assert rec["bound"] == "wire_bound"
    assert rec["bound_times_s"]["wire"] == pytest.approx(0.1)
    assert rec["bound_explains_fraction"] == pytest.approx(0.5)

    # unknown when no resource pair is complete; bad step time raises
    rec = roofline_record(flops=None, step_time_s=1e-3,
                          peak_flops_per_s=None)
    assert rec["bound"] == "unknown" and rec["mfu"] is None
    with pytest.raises(ValueError, match="step_time_s"):
        roofline_record(flops=1e9, step_time_s=0.0,
                        peak_flops_per_s=1e12)


def test_roofline_device_table_and_publish():
    v5e = device_peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == pytest.approx(197e12)
    assert v5e["hbm_bytes_per_s"] == pytest.approx(819e9)
    # exact device_kind only: no substring match, no default, no
    # calibration — an unknown device is an error
    for kind in ("TPU v5", "tpu v5 lite", "cpu", "weird accelerator"):
        with pytest.raises(ValueError, match="no published peaks"):
            device_peaks(kind)
    reg = MetricRegistry()
    rec = roofline_record(flops=1e9, step_time_s=1e-3,
                          peak_flops_per_s=2e12,
                          hbm_bytes=1e8, hbm_bytes_per_s=1e12)
    publish_roofline(rec, registry=reg)
    assert reg.get("geomx_mfu")._solo().value == pytest.approx(0.5)
    one_hot = reg.get("geomx_roofline_bound")
    assert one_hot.labels(bound="compute_bound").value == 1.0
    assert one_hot.labels(bound="wire_bound").value == 0.0
    assert reg.get("geomx_roofline_bound_seconds").labels(
        resource="compute").value == pytest.approx(5e-4)


def test_compiled_costs_from_real_compiled():
    """cost_analysis plumbing on a real compiled program (CPU backends
    that offer no analysis report available=False instead of lying)."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda a: a @ a)
    compiled = f.lower(jnp.ones((64, 64), jnp.float32)).compile()
    costs = compiled_costs(compiled)
    if costs["available"]:
        assert costs["flops"] and costs["flops"] >= 2 * 64 ** 3 * 0.5
    else:
        assert "flops" not in costs or costs["flops"] is None


# --------------------------------------------------------------------------
# links: EWMA estimators on replayed rounds
# --------------------------------------------------------------------------

def test_link_ewma_convergence_and_validation():
    obs = LinkObservatory(alpha=0.5)
    for i in range(20):
        obs.observe("p0", nbytes=1e6, seconds=0.1, t=float(i))
    snap = obs.snapshot(now=19.0)["p0->global"]
    # constant observations: the EWMA converges to the true rate
    assert snap["throughput_bps"] == pytest.approx(1e7, rel=1e-6)
    assert snap["rtt_s"] == pytest.approx(0.1, rel=1e-6)
    assert snap["loss_rate"] == pytest.approx(0.0)
    assert snap["samples"] == 20 and snap["failures"] == 0
    with pytest.raises(ValueError, match="alpha"):
        LinkObservatory(alpha=0.0)
    with pytest.raises(ValueError, match="stale_after_s"):
        LinkObservatory(stale_after_s=-1.0)


def test_link_staleness_decay():
    obs = LinkObservatory(stale_after_s=30.0)
    obs.observe("p0", nbytes=1e6, seconds=0.1, t=1000.0)
    fresh = obs.snapshot(now=1000.0)["p0->global"]
    assert fresh["confidence"] == pytest.approx(1.0)
    assert not fresh["stale"]
    one_hl = obs.snapshot(now=1030.0)["p0->global"]
    assert one_hl["confidence"] == pytest.approx(0.5)
    two_hl = obs.snapshot(now=1060.0)["p0->global"]
    assert two_hl["confidence"] == pytest.approx(0.25)
    assert two_hl["stale"]
    # a link never observed at all has zero confidence
    obs2 = LinkObservatory()
    assert obs2.snapshot() == {}


def test_link_replay_of_chaos_rounds_is_deterministic():
    """Replay WAN rounds patterned on a chaos schedule (party 1 blacked
    out for rounds 3..5 -> RelayFailure instants): the loss EWMA rises
    through the blackout and decays on recovery, and replaying the
    same trace twice produces identical snapshots."""
    from geomx_tpu.resilience.chaos import ChaosSchedule

    sched = ChaosSchedule.from_spec("seed=7;blackout@3:party=1,steps=3")
    blacked = set()
    dead = False
    for step in range(10):
        for e in sched.events_at(step):
            dead = e.kind == "blackout" if e.party == 1 else dead
        if dead:
            blacked.add(step)
    assert blacked == {3, 4, 5}

    def trace():
        events = []
        for r in range(10):
            ts = r * 2e5
            if r in blacked:
                events.append({"name": "RelayFailure:w", "cat": "comm",
                               "ph": "i", "ts": ts, "pid": 1, "tid": 1,
                               "args": {"key": "w", "round_id": r}})
            else:
                events.append(_span("RelayToGlobal:w", "comm", ts, 1e5,
                                    args={"key": "w", "round_id": r,
                                          "payload_bytes": 1 << 20}))
        return {"traceEvents": events,
                "metadata": {"anchor_unix_us": 1e15, "rank": 1}}

    obs_a, obs_b = LinkObservatory(alpha=0.3), LinkObservatory(alpha=0.3)
    assert obs_a.ingest_trace(trace()) == 10
    obs_b.ingest_trace(trace())
    now = 1e15 / 1e6 + 3.0
    snap_a = obs_a.snapshot(now=now)
    assert snap_a == obs_b.snapshot(now=now)   # deterministic replay
    link = snap_a["rank1->global"]
    assert link["failures"] == 3 and link["samples"] == 10
    # the blackout pushed loss up; four clean rounds pulled it back
    # below the mid-blackout peak but not to zero
    assert 0.0 < link["loss_rate"] < 0.5
    # loss EWMA mid-blackout (after 3 straight failures) for contrast
    obs_mid = LinkObservatory(alpha=0.3)
    for r in range(6):
        obs_mid.observe("rank1", ok=(r not in blacked), t=float(r))
    assert obs_mid.snapshot(now=6.0)["rank1->global"]["loss_rate"] > \
        link["loss_rate"]


def test_link_asymmetry_reproduced_from_replay():
    """The acceptance case: injected 8x per-link bandwidth asymmetry in
    replayed round traces shows up as an 8x throughput ratio in the
    snapshot."""
    obs = LinkObservatory()
    payload = 1 << 20
    for rank, secs in ((0, 0.05), (1, 0.4)):
        events = [_span("RelayToGlobal:w", "comm", r * 1e6, secs * 1e6,
                        args={"payload_bytes": payload, "round_id": r})
                  for r in range(5)]
        obs.ingest_trace({"traceEvents": events,
                          "metadata": {"anchor_unix_us": 0,
                                       "rank": rank}})
    snap = obs.snapshot(now=10.0)
    ratio = (snap["rank0->global"]["throughput_bps"]
             / snap["rank1->global"]["throughput_bps"])
    assert ratio == pytest.approx(8.0, rel=1e-6)


def test_link_ingest_merged_trace_uses_process_names():
    """A merge_traces document names parties via process_name metadata
    rows; ingest must key links on those labels."""
    events = [
        {"name": "process_name", "ph": "M", "pid": 7,
         "args": {"name": "party0"}},
        _span("RelayToGlobal:w", "comm", 0.0, 1e5, pid=7,
              args={"payload_bytes": 4096}),
    ]
    obs = LinkObservatory()
    assert obs.ingest_trace({"traceEvents": events}) == 1
    assert list(obs.snapshot(now=1.0)) == ["party0->global"]


# --------------------------------------------------------------------------
# flight recorder: ring + anomaly rules + forensics bundle
# --------------------------------------------------------------------------

def _healthy(step, norm=1.0, density=0.01):
    return {"grad_norm_global": norm, "grad_all_finite": 1.0,
            "party_grad_nonfinite": [0.0, 0.0],
            "dc_nonzero_fraction": density}


def test_flight_ring_is_bounded():
    rec = FlightRecorder(capacity=4)
    for i in range(10):
        rec.record(i, _healthy(i))
    ring = rec.snapshot()
    assert len(ring) == 4
    assert [r["step"] for r in ring] == [6, 7, 8, 9]
    with pytest.raises(ValueError, match="capacity"):
        FlightRecorder(capacity=0)


def test_flight_nonfinite_autodump_names_poisoned_party(tmp_path):
    """Acceptance: a NaN injection at a known step fires the nonfinite
    rule deterministically and the bundle names the poisoned party."""
    d = str(tmp_path / "flight")
    runs = []
    for _ in range(2):   # determinism: identical sequences, identical firing
        rec = FlightRecorder(capacity=16, dump_dir=d)
        fired_log = []
        for i in range(8):
            fired_log.append(rec.record(i, _healthy(i)))
        poisoned = {"grad_norm_global": float("nan"),
                    "grad_all_finite": 0.0,
                    "party_grad_nonfinite": [0.0, 1.0],
                    "dc_nonzero_fraction": 0.01}
        fired_log.append(rec.record(8, poisoned))
        runs.append((fired_log, list(rec.dumps)))
    (fired_a, dumps_a), (fired_b, dumps_b) = runs
    assert fired_a == fired_b
    assert all(not f for f in fired_a[:8])
    fired = fired_a[8]
    assert [f["rule"] for f in fired] == [NONFINITE]
    assert fired[0]["poisoned_parties"] == [1]
    assert "grad_norm_global" in fired[0]["nonfinite_probes"]
    assert dumps_a == dumps_b == [os.path.join(
        d, "flight_step8_nonfinite_probe.json")]
    with open(dumps_a[0]) as f:
        bundle = json.load(f)
    assert bundle["kind"] == "geomx_flight_bundle"
    assert bundle["step"] == 8
    assert bundle["poisoned_parties"] == [1]
    assert len(bundle["ring"]) == 9
    assert bundle["ring"][-1]["anomalies"][0]["rule"] == NONFINITE


def test_flight_grad_spike_rule():
    rec = FlightRecorder(capacity=32, spike_factor=10.0, min_history=5)
    for i in range(6):
        assert rec.record(i, _healthy(i, norm=1.0 + 0.01 * i)) == []
    # 3x the median is loud but below the spike factor: quiet
    assert rec.record(6, _healthy(6, norm=3.0)) == []
    fired = rec.record(7, _healthy(7, norm=50.0))
    assert [f["rule"] for f in fired] == [GRAD_SPIKE]
    assert fired[0]["factor"] > 10.0
    # too little history: the rule stays quiet (fresh runs aren't
    # anomalies)
    young = FlightRecorder(capacity=32, min_history=5)
    young.record(0, _healthy(0, norm=1.0))
    assert young.record(1, _healthy(1, norm=100.0)) == []


def test_flight_density_drift_rule():
    rec = FlightRecorder(capacity=32, density_drift=0.5, min_history=5)
    for i in range(6):
        assert rec.record(i, _healthy(i, density=0.010)) == []
    assert rec.record(6, _healthy(6, density=0.012)) == []   # in band
    fired = rec.record(7, _healthy(7, density=0.10))
    assert [f["rule"] for f in fired] == [DENSITY_DRIFT]
    assert fired[0]["relative_drift"] > 0.5


def test_flight_exposed_comms_jump_rule():
    rec = FlightRecorder(capacity=32, exposed_jump=0.25, min_history=5)
    for i in range(6):
        assert rec.record(i, _healthy(i),
                          phases={"exposed_comms": 0.05}) == []
    fired = rec.record(6, _healthy(6), phases={"exposed_comms": 0.60})
    assert [f["rule"] for f in fired] == [EXPOSED_JUMP]
    assert fired[0]["jump"] == pytest.approx(0.55)


def test_flight_recorder_from_config_and_env(monkeypatch):
    assert flight_recorder_from_config(GeoConfig()) is None
    monkeypatch.delenv("GEOMX_FLIGHT", raising=False)
    assert flight_recorder_from_config(None) is None
    rec = flight_recorder_from_config(
        GeoConfig(flight=True, flight_steps=7, flight_dir="/tmp/fx"))
    assert rec.capacity == 7 and rec.dump_dir == "/tmp/fx"
    monkeypatch.setenv("GEOMX_FLIGHT", "1")
    monkeypatch.setenv("GEOMX_FLIGHT_STEPS", "11")
    monkeypatch.setenv("GEOMX_FLIGHT_SPIKE", "4.5")
    rec = flight_recorder_from_config(None)
    assert rec.capacity == 11 and rec.spike_factor == 4.5


def _mini_trainer(**cfg_kw):
    topo = HiPSTopology(num_parties=2, workers_per_party=1)
    cfg = GeoConfig(num_parties=2, workers_per_party=1,
                    compression="bsc,0.05,min_sparse_size=16", **cfg_kw)
    return Trainer(MLP(num_classes=10, hidden=(32,)), topo,
                   optax.sgd(0.1), sync=get_sync_algorithm(cfg),
                   config=cfg, donate=False)


def test_trainer_flight_warns_without_telemetry():
    with pytest.warns(RuntimeWarning, match="GEOMX_FLIGHT"):
        _mini_trainer(flight=True, telemetry=False)


def test_trainer_publish_feeds_flight_ring(tmp_path):
    """The trainer records every published probe set into the flight
    ring at the existing log boundary, membership epoch included."""
    import jax

    tr = _mini_trainer(telemetry=True, flight=True,
                       flight_dir=str(tmp_path / "fl"))
    assert tr._flight is not None
    rng = np.random.RandomState(0)
    x = (rng.rand(2, 1, 4, 8, 8, 3) * 255).astype(np.uint8)
    y = rng.randint(0, 10, size=(2, 1, 4)).astype(np.int32)
    sharding = tr.topology.batch_sharding(tr.mesh)
    xb, yb = jax.device_put(x, sharding), jax.device_put(y, sharding)
    state = tr.init_state(jax.random.PRNGKey(0), x[0, 0, :2])
    for it in (1, 2):
        state, m = tr.train_step(state, xb, yb)
        tr._publish_telemetry(jax.device_get(m["telemetry"]), it)
    ring = tr._flight.snapshot()
    assert [r["step"] for r in ring] == [1, 2]
    assert all(math.isfinite(r["probes"]["grad_norm_global"])
               for r in ring)
    assert ring[-1]["membership_version"] == tr._membership_version
    assert tr._flight.dumps == []   # healthy run: no forensics bundle


def test_trainer_flight_records_carry_scoped_phase_breakdown(tmp_path):
    """The wired publish path feeds a phase summary into every flight
    record (the exposed_comms_jump rule's input), attributed over a
    window that restarts at each publish — spans from earlier profiled
    work (a previous fit, a warm-up) must not leak into it."""
    import jax

    from geomx_tpu.utils.profiler import get_profiler

    tr = _mini_trainer(telemetry=True, flight=True,
                       flight_dir=str(tmp_path / "fl"))
    rng = np.random.RandomState(0)
    x = (rng.rand(2, 1, 4, 8, 8, 3) * 255).astype(np.uint8)
    y = rng.randint(0, 10, size=(2, 1, 4)).astype(np.int32)
    sharding = tr.topology.batch_sharding(tr.mesh)
    xb, yb = jax.device_put(x, sharding), jax.device_put(y, sharding)
    state = tr.init_state(jax.random.PRNGKey(0), x[0, 0, :2])
    prof = get_profiler()
    prof.reset()
    prof.set_state(True)
    try:
        # "earlier work": a 4-second fully-exposed step long before this
        # fit — an unscoped attribution would read ~100% exposed_comms
        t0 = prof.now_us()
        prof.add_event("train/step", t0 - 5e6, t0 - 1e6, category="step")
        prof.add_event("RelayToGlobal:old", t0 - 5e6, t0 - 1e6,
                       category="comm")
        tr._attr_window_us = prof.now_us()  # what fit marks at its start
        for it in (1, 2):
            with prof.scope("train/step", "step", args={"step": it}):
                with prof.scope("train/compute", "compute"):
                    state, m = tr.train_step(state, xb, yb)
            tr._publish_telemetry(jax.device_get(m["telemetry"]), it)
        ring = tr._flight.snapshot()
        assert len(ring) == 2 and all("phases" in r for r in ring)
        for r in ring:
            ph = r["phases"]
            assert sum(ph.values()) == pytest.approx(1.0)
            # the stale exposed step was before the window mark
            assert ph["exposed_comms"] < 0.1
            assert ph["compute"] > 0.5
    finally:
        prof.set_state(False)
        prof.reset()


# --------------------------------------------------------------------------
# satellites: profiler accounting, event-log rotations, /healthz
# --------------------------------------------------------------------------

def test_profiler_dump_metadata_span_and_drop_accounting(tmp_path):
    p = Profiler(filename=str(tmp_path / "t.json"), max_events=3)
    p.set_state(True)
    for i in range(5):
        with p.scope(f"s{i}", "host"):
            pass
    p.instant("late", "host")
    doc = json.loads(open(p.dump()).read())
    md = doc["metadata"]
    # 3 kept events + the thread_name metadata row
    assert md["num_spans"] == 3
    assert md["dropped_events"] == 3
    assert md["num_events"] == len(doc["traceEvents"])
    p.reset()
    md2 = p.to_doc()["metadata"]
    assert md2["num_spans"] == 0 and md2["dropped_events"] == 0


def test_eventlog_rotation_publishes_counter(tmp_path):
    from geomx_tpu.telemetry import EventLog, get_registry, reset_registry

    reset_registry()
    log = EventLog(str(tmp_path / "ev.jsonl"), max_bytes=512)
    for i in range(200):
        log.emit("tick", i=i, pad="x" * 64)
    assert log.rotations >= 1
    c = get_registry().get("geomx_eventlog_rotations_total")
    assert c._solo().value == log.rotations
    reset_registry()


def test_scheduler_healthz_and_build_info():
    sched = GeoScheduler(metrics_port=0).start()
    try:
        c = SchedulerClient(("127.0.0.1", sched.port))
        c.register("worker", tag="0.0")
        c.heartbeat()
        with urllib.request.urlopen(
                f"http://127.0.0.1:{sched.metrics_port}/healthz",
                timeout=10) as resp:
            assert resp.status == 200
            assert resp.headers["Content-Type"] == "application/json"
            health = json.loads(resp.read())
        assert health["status"] == "ok"
        assert health["roster_epoch"] >= 1
        assert health["roster"].get("worker") == 1
        assert health["live_parties"] >= 1
        assert health["dead_parties"] == 0
        assert health["uptime_s"] >= 0.0
        from geomx_tpu import __version__
        assert health["build"]["version"] == __version__
        # build identity rides /metrics as the constant-1 info gauge
        with urllib.request.urlopen(
                f"http://127.0.0.1:{sched.metrics_port}/metrics",
                timeout=10) as resp:
            fams = parse_prometheus_text(resp.read().decode())
        info = fams["geomx_build_info"]["samples"]
        assert info and info[0][2] == 1.0
        assert info[0][1]["version"] == __version__
        assert info[0][1]["jax_version"]
        c.close()
    finally:
        sched.stop()
