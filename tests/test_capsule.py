"""Run capsules (telemetry/capsule.py, docs/telemetry.md "Run
capsules"): whole-run capture, bit-exact offline replay of the
LinkObservatory snapshot and the ControlSensors observation stream /
GraftPilot decision sequence, the fitted step-time cost model
(telemetry/costmodel.py), the runcap CLI, and the ride-along
satellites — the shared atomic-write owner (utils/atomicio.py), the
flight-bundle registry section, the event-log dropped-records counter,
and observatory replay equivalence (ingest_trace vs ingest_ledger).
These tests pin the mechanisms in milliseconds.
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

from geomx_tpu.telemetry import reset_registry
from geomx_tpu.telemetry.capsule import (Capsule, RegistrySampler,
                                         RunCapsule, capsule_from_config,
                                         sample_registry)
from geomx_tpu.telemetry.costmodel import (StepTimeCostModel,
                                           fit_affine_link,
                                           fit_paired_link)
from geomx_tpu.telemetry.links import LinkObservatory

TOOLS = os.path.join(os.path.dirname(__file__), "..", "tools")


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(TOOLS, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture()
def registry():
    reg = reset_registry()
    yield reg
    reset_registry()


# ---- utils/atomicio (satellite: the one atomic-write owner) ---------------


def test_atomic_write_bytes_and_json(tmp_path):
    from geomx_tpu.utils.atomicio import (atomic_json_dump,
                                          atomic_write_bytes)
    p = tmp_path / "a.bin"
    atomic_write_bytes(str(p), b"hello", fsync=True)
    assert p.read_bytes() == b"hello"
    q = tmp_path / "sub" / "b.json"   # creates the directory
    atomic_json_dump(str(q), {"x": 1})
    assert json.loads(q.read_text()) == {"x": 1}
    # no temp litter
    assert [f for f in os.listdir(tmp_path) if f.startswith(".atomic")] \
        == []


def test_atomic_replace_failure_preserves_previous(tmp_path):
    from geomx_tpu.utils.atomicio import atomic_replace
    p = tmp_path / "f.txt"
    p.write_text("old")
    with pytest.raises(RuntimeError):
        with atomic_replace(str(p), "w") as f:
            f.write("half-written")
            raise RuntimeError("crash mid-dump")
    assert p.read_text() == "old"
    assert [f for f in os.listdir(tmp_path) if f.startswith(".atomic")] \
        == []


def test_sweep_stale_tmp_reclaims_orphans_only(tmp_path):
    from geomx_tpu.utils.atomicio import sweep_stale_tmp
    stale = tmp_path / ".atomic_dead.tmp"
    stale.write_bytes(b"orphan")
    os.utime(stale, (1, 1))                  # ancient mtime
    fresh = tmp_path / ".atomic_live.tmp"
    fresh.write_bytes(b"in flight")          # a live writer's temp
    other = tmp_path / "keep.tmp"
    other.write_bytes(b"not ours")
    assert sweep_stale_tmp(str(tmp_path)) == 1
    assert not stale.exists() and fresh.exists() and other.exists()
    # the durable store's constructor reclaims on restart
    from geomx_tpu.resilience.durability import DurableStateStore
    os.utime(fresh, (1, 1))
    DurableStateStore(str(tmp_path), "node0")
    assert not fresh.exists()


def test_registry_sampler_clamps_nonpositive_interval(registry):
    assert RegistrySampler(registry, interval_s=0.0).interval_s == 10.0
    assert RegistrySampler(registry, interval_s=-1).interval_s == 10.0
    assert RegistrySampler(registry, interval_s=2.5).interval_s == 2.5


def test_durable_store_still_roundtrips_via_shared_owner(tmp_path):
    # durability._atomic_write now delegates to atomicio — the store's
    # snapshot semantics must be unchanged
    from geomx_tpu.resilience.durability import DurableStateStore
    st = DurableStateStore(str(tmp_path), "node0")
    st.snapshot({"a": 1})
    st.append({"op": "x"})
    st2 = DurableStateStore(str(tmp_path), "node0")
    snap, records = st2.load()
    assert snap == {"a": 1} and [r["op"] for r in records] == ["x"]


# ---- registry sampling ----------------------------------------------------


def test_sample_registry_all_types_and_bound(registry):
    registry.counter("geomx_c_total").inc(3)
    g = registry.gauge("geomx_g", labels=("who",))
    for i in range(6):
        g.labels(who=f"p{i}").set(float(i))
    registry.histogram("geomx_h").observe(0.03)
    snap = sample_registry(registry)
    assert snap["geomx_c_total"]["children"][0]["value"] == 3.0
    assert len(snap["geomx_g"]["children"]) == 6
    h = snap["geomx_h"]["children"][0]
    assert h["count"] == 1 and len(h["counts"]) == len(h["buckets"]) + 1
    bounded = sample_registry(registry, max_children_per_family=2)
    assert len(bounded["geomx_g"]["children"]) == 2
    assert bounded["geomx_g"]["dropped_children"] == 4


def test_registry_sampler_manual_and_loop(registry):
    registry.gauge("geomx_x").set(7.0)
    s = RegistrySampler(registry, interval_s=0.01, max_samples=3)
    s.sample(now=1.0)
    s.sample(now=2.0)
    assert [e["t"] for e in s.snapshot()] == [1.0, 2.0]
    for t in (3.0, 4.0):
        s.sample(now=t)
    assert len(s.snapshot()) == 3 and s.dropped == 1   # bounded ring
    s.start()
    import time
    deadline = time.time() + 2.0
    while len(s.snapshot()) < 4 and time.time() < deadline:
        time.sleep(0.01)
    s.stop()
    assert len(s.snapshot()) >= 3   # the loop sampled on its own


# ---- capsule record -> load -> bit-identical replay ----------------------


def _feed_obs(obs, fail_step=7, steps=10):
    for i in range(steps):
        t = float(i)
        obs.observe("party0", nbytes=1e6, seconds=0.02 + 0.001 * i, t=t)
        ok = i != fail_step
        obs.observe("party1", nbytes=1e6,
                    seconds=0.3 if i >= 5 else 0.04, ok=ok, t=t)


def test_capsule_link_snapshot_bit_identical(tmp_path, registry):
    obs = LinkObservatory(alpha=0.4, stale_after_s=5.0)
    cap = RunCapsule(str(tmp_path / "c.json"))
    cap.attach_observatory(obs)
    _feed_obs(obs)
    live = obs.snapshot(now=9.0)
    path = cap.write(now=9.0)
    loaded = Capsule.load(path)
    assert json.dumps(loaded.link_snapshot(now=9.0), sort_keys=True) \
        == json.dumps(live, sort_keys=True)
    # mid-run instants replay bit-identically too (no future leakage:
    # the live observatory at t=4 had only the first 5 rounds)
    obs2 = LinkObservatory(alpha=0.4, stale_after_s=5.0)
    for i in range(5):
        t = float(i)
        obs2.observe("party0", nbytes=1e6, seconds=0.02 + 0.001 * i, t=t)
        obs2.observe("party1", nbytes=1e6, seconds=0.04, t=t)
    assert json.dumps(loaded.link_snapshot(now=4.0), sort_keys=True) \
        == json.dumps(obs2.snapshot(now=4.0), sort_keys=True)


def test_capsule_manifest_and_sections(tmp_path, registry, monkeypatch):
    monkeypatch.setenv("GEOMX_TEST_KNOB", "42")
    from geomx_tpu.config import GeoConfig
    cfg = GeoConfig(telemetry=True, chaos_schedule="seed=3")
    cap = RunCapsule(str(tmp_path / "c.json"), config=cfg,
                     extra_manifest={"note": "unit"})
    registry.gauge("geomx_step_probe", labels=("probe",)).labels(
        probe="grad_norm_global").set(1.5)
    cap.record_step(0, t=0.5, timing={"total_s": 0.1})
    cap.sampler.sample(now=0.5)
    loaded = Capsule.load(cap.write(now=0.5))
    m = loaded.manifest
    assert m["kind"] == "geomx_run_capsule" and m["version"] == 1
    assert m["config"]["telemetry"] is True
    assert m["chaos_schedule"] == "seed=3"
    assert m["env"]["GEOMX_TEST_KNOB"] == "42"
    assert m["extra"]["note"] == "unit"
    assert m["build"]["python"]
    assert loaded.steps[0]["probes"]["grad_norm_global"] == 1.5
    assert loaded.registry_samples[0]["t"] == 0.5


def test_capsule_unknown_version_rejected(tmp_path):
    cap = RunCapsule(str(tmp_path / "c.json"))
    path = cap.write()
    doc = json.load(open(path))
    doc["manifest"]["version"] = 99
    with pytest.raises(ValueError, match="version"):
        Capsule(doc)
    with pytest.raises(ValueError, match="not a run capsule"):
        Capsule({"manifest": {"kind": "something_else"}})


def test_capsule_sensor_stream_bit_identical(tmp_path, registry):
    from geomx_tpu.control.sensors import ControlSensors
    obs = LinkObservatory()
    cap = RunCapsule(str(tmp_path / "c.json"))
    cap.attach_observatory(obs)
    fam = registry.gauge("geomx_step_probe", labels=("probe",))
    pfam = registry.gauge("geomx_phase_fraction", labels=("phase",))
    live_sensors = ControlSensors(observatory=obs, registry=registry,
                                  min_confidence=0.5)
    live_obs = []
    for i in range(8):
        t = float(i)
        fam.labels(probe="grad_norm_global").set(1.0 + i)
        fam.labels(probe="dc_wire_bytes").set(1e6)
        pfam.labels(phase="exposed_comms").set(0.1 * i)
        pfam.labels(phase="compute").set(1.0 - 0.1 * i)
        obs.observe("party0", nbytes=1e6, seconds=0.05, t=t)
        obs.observe("party1", nbytes=1e6,
                    seconds=0.5 if i >= 4 else 0.05, t=t)
        cap.record_step(i, t=t)
        live_obs.append(live_sensors.observe(i, now=t))
    loaded = Capsule.load(cap.write(now=7.0))
    replay_sensors = loaded.sensors(min_confidence=0.5)
    for i, rec in enumerate(loaded.steps):
        assert replay_sensors.observe(rec["step"], now=rec["t"]) \
            == live_obs[i]


def test_capsule_pilot_replay_reproduces_decisions(tmp_path, registry):
    from geomx_tpu.control import (ControlSensors, DepthPolicy,
                                   GraftPilot, RelayPolicy)
    obs = LinkObservatory()
    cap = RunCapsule(str(tmp_path / "c.json"))
    cap.attach_observatory(obs)
    pfam = registry.gauge("geomx_phase_fraction", labels=("phase",))

    def factory(sensors):
        return GraftPilot(
            sensors,
            depth=DepthPolicy(enter=0.45, exit=0.35, confirm=2,
                              cooldown=2),
            relay=RelayPolicy(min_gain=2.0, cooldown=2,
                              min_confidence=0.5))

    live_pilot = factory(ControlSensors(observatory=obs,
                                        registry=registry,
                                        min_confidence=0.5))
    live_decisions = []
    for i in range(16):
        t = float(i)
        degraded = 4 <= i < 12
        pfam.labels(phase="exposed_comms").set(0.6 if degraded else 0.1)
        pfam.labels(phase="hidden_comms").set(0.0)
        obs.observe("party0", nbytes=1e6, seconds=0.01, t=t)
        obs.observe("party1", nbytes=1e6,
                    seconds=0.4 if degraded else 0.012, t=t)
        obs.observe("party2", nbytes=1e6, seconds=0.011, t=t)
        cap.record_step(i, t=t)
        live_decisions.extend(d.to_json()
                              for d in live_pilot.tick(i, now=t))
    assert live_decisions, "scenario must actually produce decisions"
    loaded = Capsule.load(cap.write(now=15.0))
    replayed = loaded.replay_decisions(factory, min_confidence=0.5)
    assert json.dumps(replayed, sort_keys=True) \
        == json.dumps(live_decisions, sort_keys=True)


def test_capsule_from_config_gating(tmp_path, monkeypatch):
    assert capsule_from_config(None) is None
    monkeypatch.setenv("GEOMX_CAPSULE", "1")
    monkeypatch.setenv("GEOMX_CAPSULE_DIR", str(tmp_path / "caps"))
    monkeypatch.setenv("GEOMX_CAPSULE_SAMPLE_S", "2.5")
    cap = capsule_from_config(None)
    assert cap is not None
    assert cap.path == str(tmp_path / "caps" / "run_capsule.json")
    assert cap.sampler.interval_s == 2.5
    from geomx_tpu.config import GeoConfig
    cap2 = capsule_from_config(GeoConfig(capsule=True,
                                         capsule_dir=str(tmp_path)))
    monkeypatch.delenv("GEOMX_CAPSULE")
    assert cap2.path == str(tmp_path / "run_capsule.json")


# ---- observatory replay equivalence (satellite) ---------------------------


def test_ingest_trace_and_ingest_ledger_agree():
    """The same rounds fed through the trace path and the ledger path
    produce consistent per-link snapshots: identical observation
    streams -> identical EWMA state."""
    rounds = [  # (party, t, dur_s, nbytes)
        (0, 10.0, 0.05, 1e6), (1, 10.0, 0.40, 1e6),
        (0, 11.0, 0.06, 1e6), (1, 11.0, 0.38, 1e6),
    ]
    anchor_us = 10.0 * 1e6
    trace = {"metadata": {"anchor_unix_us": anchor_us, "rank": None},
             "traceEvents": []}
    ledger_records = {}
    for party, t, dur, nb in rounds:
        trace["traceEvents"].append({
            "name": f"RelayToGlobal:w{party}", "ph": "X",
            "ts": t * 1e6 - anchor_us, "dur": dur * 1e6, "pid": 1,
            "args": {"payload_bytes": nb}})
        rec = ledger_records.setdefault((party, t), {
            "status": "complete", "hops": []})
        rec["hops"].append({"hop": "relay", "party": party, "t": t,
                            "dur_s": dur, "nbytes": nb})
    # the trace path needs a party name per pid-less dump: feed one
    # doc per party so the default-party attribution matches
    obs_trace = LinkObservatory()
    for party in (0, 1):
        doc = {"metadata": trace["metadata"],
               "traceEvents": [ev for ev in trace["traceEvents"]
                               if ev["name"].endswith(f"w{party}")]}
        assert obs_trace.ingest_trace(doc, party=f"party{party}") == 2
    obs_ledger = LinkObservatory()
    assert obs_ledger.ingest_ledger(list(ledger_records.values())) == 4
    snap_t = obs_trace.snapshot(now=11.0)
    snap_l = obs_ledger.snapshot(now=11.0)
    assert json.dumps(snap_t, sort_keys=True) \
        == json.dumps(snap_l, sort_keys=True)


# ---- cost model -----------------------------------------------------------


def test_fit_affine_link_recovers_parameters():
    a, ib = 0.02, 1e-8
    samples = [{"t": float(i), "nbytes": b, "seconds": a + b * ib,
                "ok": True}
               for i, b in enumerate([1e5, 5e5, 1e6, 2e6, 4e6])]
    fit = fit_affine_link(samples)
    assert fit["latency_s"] == pytest.approx(a, rel=1e-6)
    assert fit["sec_per_byte"] == pytest.approx(ib, rel=1e-6)
    assert all(s["resid"] == pytest.approx(1.0) for s in fit["samples"])
    # degenerate spread: one payload size -> zero-latency fallback
    flat = [{"t": float(i), "nbytes": 1e6, "seconds": 0.03, "ok": True}
            for i in range(4)]
    fit = fit_affine_link(flat)
    assert fit["latency_s"] == 0.0
    assert fit["sec_per_byte"] == pytest.approx(0.03 / 1e6)


def test_fit_paired_link_solves_per_step_exactly():
    # shaped link: latency and bandwidth both change mid-run
    def params(i):
        return (0.16, 4e-8) if i >= 3 else (0.01, 5e-9)

    payload, probe = [], []
    for i in range(6):
        a, ib = params(i)
        payload.append({"t": float(i), "nbytes": 1e6,
                        "seconds": a + 1e6 * ib, "ok": True})
        probe.append({"t": float(i), "nbytes": 4096.0,
                      "seconds": a + 4096.0 * ib, "ok": True})
    fit = fit_paired_link(payload, probe)
    assert fit["num_samples"] == 6
    for i, e in enumerate(fit["timeline"]):
        a, ib = params(i)
        assert e["latency_s"] == pytest.approx(a, rel=1e-9)
        assert e["sec_per_byte"] == pytest.approx(ib, rel=1e-9)
    assert fit_paired_link(payload, []) is None   # no probes -> fallback


def test_cost_model_predict_depth_and_window_alignment():
    timeline = [{"t": float(i), "latency_s": 0.2 if i >= 3 else 0.01,
                 "sec_per_byte": 1e-8} for i in range(6)]
    links = {"party0": {"latency_s": 0.01, "sec_per_byte": 1e-8,
                        "num_samples": 6, "timeline": timeline}}
    m = StepTimeCostModel(links, compute_s=0.05,
                          step_times=[float(i) for i in range(6)])
    d0 = m.predict({"wire_bytes": 1e6, "depth": 0})
    d1 = m.predict({"wire_bytes": 1e6, "depth": 1})
    # healthy steps: wan = 0.02 fully hidden at depth 1; degraded
    # steps: wan = 0.21, exposed 0.16 at depth 1
    assert d0["mean_step_s"] == pytest.approx(
        (3 * (0.05 + 0.02) + 3 * (0.05 + 0.21)) / 6)
    assert d1["mean_step_s"] == pytest.approx(
        (3 * 0.05 + 3 * (0.05 + 0.16)) / 6)
    assert d1["mean_step_s"] < d0["mean_step_s"]
    big = m.predict({"wire_bytes": 1e7, "depth": 0})
    assert big["mean_step_s"] > d0["mean_step_s"]


def test_candidate_wire_bytes_matches_compressor_accounting():
    import jax

    from geomx_tpu.compression.bisparse import BiSparseCompressor
    from geomx_tpu.compression.bucketing import BucketedCompressor
    from geomx_tpu.telemetry.costmodel import candidate_wire_bytes
    shapes = {"w1": {"shape": [256, 64], "dtype": "float32"},
              "b1": {"shape": [64], "dtype": "float32"}}
    tree = {k: jax.ShapeDtypeStruct(tuple(v["shape"]), v["dtype"])
            for k, v in shapes.items()}
    want = BucketedCompressor(BiSparseCompressor(ratio=0.25),
                              bucket_bytes=1 << 20).wire_bytes(tree)
    got = candidate_wire_bytes(shapes, "bsc,0.25", 1 << 20)
    assert got == float(want)
    dense = candidate_wire_bytes(shapes, "none", 0)
    assert dense == 4 * 256 * 64 + 4 * 64


def test_cost_model_fit_skips_dead_party(tmp_path, registry):
    """A party whose every observation failed (link dead for the whole
    run) is skipped — the model still fits the live parties."""
    obs = LinkObservatory()
    cap = RunCapsule(str(tmp_path / "c.json"))
    cap.attach_observatory(obs)
    for i in range(4):
        t = float(i)
        obs.observe("party0", nbytes=1e6, seconds=0.05, t=t)
        obs.observe("party1", ok=False, t=t)   # dead: loss-only
        cap.record_step(i, t=t, timing={"total_s": 0.08,
                                        "compute_s": 0.05})
    m = StepTimeCostModel.fit(Capsule.load(cap.write(now=3.0)))
    assert sorted(m.links) == ["party0"]
    assert m.skipped_links == ["party1"]
    assert m.to_json()["skipped_links"] == ["party1"]
    assert m.predict({"wire_bytes": 1e6, "depth": 0})["mean_step_s"] > 0


def test_cost_model_fit_from_capsule(tmp_path, registry):
    obs = LinkObservatory()
    cap = RunCapsule(str(tmp_path / "c.json"))
    cap.attach_observatory(obs)
    for i in range(5):
        t = float(i)
        obs.observe("party0", nbytes=1e6, seconds=0.01 + 1e6 * 1e-8,
                    t=t)
        obs.observe("party0", "probe", nbytes=4096.0,
                    seconds=0.01 + 4096.0 * 1e-8, t=t)
        cap.record_step(i, t=t, timing={"total_s": 0.07,
                                        "compute_s": 0.05})
    m = StepTimeCostModel.fit(Capsule.load(cap.write(now=4.0)))
    assert m.compute_s == pytest.approx(0.05)
    assert "timeline" in m.links["party0"]
    pred = m.predict({"wire_bytes": 2e6, "depth": 0})
    assert pred["mean_step_s"] == pytest.approx(0.05 + 0.01 + 2e6 * 1e-8)


# ---- runcap CLI -----------------------------------------------------------


def _two_capsules(tmp_path, registry):
    """A clean and a degraded capsule sharing shape: party1's uplink
    collapses and the exposed phase grows in the second."""
    paths = []
    for label, slow in (("clean", 0.05), ("bad", 0.6)):
        reset_registry()
        import geomx_tpu.telemetry.registry as _r
        reg = _r.get_registry()
        obs = LinkObservatory()
        cap = RunCapsule(str(tmp_path / f"{label}.json"))
        cap.attach_observatory(obs)
        pfam = reg.gauge("geomx_phase_fraction", labels=("phase",))
        fam = reg.gauge("geomx_step_probe", labels=("probe",))
        for i in range(6):
            t = float(i)
            obs.observe("party0", nbytes=1e6, seconds=0.05, t=t)
            obs.observe("party1", nbytes=1e6, seconds=slow, t=t)
            pfam.labels(phase="exposed_comms").set(
                0.5 if slow > 0.1 else 0.1)
            pfam.labels(phase="compute").set(
                0.5 if slow > 0.1 else 0.9)
            fam.labels(probe="grad_norm_global").set(1.0)
            cap.record_step(i, t=t)
        paths.append(cap.write(now=5.0))
    return paths


def test_runcap_diff_and_explain(tmp_path, registry):
    clean, bad = _two_capsules(tmp_path, registry)
    runcap = _load_tool("runcap")
    a, b = runcap.load_doc(clean), runcap.load_doc(bad)
    d = runcap.diff_docs(a, b)
    assert d["phases"]["exposed_comms"]["delta"] == pytest.approx(0.4)
    assert d["links"]["party1->global"]["throughput_bps"]["rel"] < -0.5
    findings = runcap.explain_docs(a, b)
    assert any(f["kind"] == "link" and f["name"] == "party1->global"
               and f["metric"] in ("throughput_bps", "rtt_s")
               for f in findings)
    assert any(f["kind"] == "phase" and f["name"] == "exposed_comms"
               for f in findings)
    # no self-findings
    assert runcap.explain_docs(a, a) == []


def test_runcap_cli_and_stdlib_only(tmp_path, registry):
    clean, bad = _two_capsules(tmp_path, registry)
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(sys.path))
    out = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "runcap.py"), "explain",
         clean, bad], capture_output=True, text=True, env=env)
    assert out.returncode == 0 and "party1" in out.stdout
    info = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "runcap.py"), "info",
         clean], capture_output=True, text=True, env=env)
    assert json.loads(info.stdout)["num_steps"] == 6
    bad_rc = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "runcap.py"), "info",
         str(tmp_path / "missing.json")], capture_output=True,
        text=True, env=env)
    assert bad_rc.returncode == 2
    # diff/explain/info never import the repo (stdlib-only readers)
    probe = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, sys.argv[1]); import runcap; "
         "assert not any(m.startswith('geomx') for m in sys.modules), "
         "sorted(m for m in sys.modules if m.startswith('geomx'))",
         TOOLS], capture_output=True, text=True)
    assert probe.returncode == 0, probe.stderr


# ---- flight bundle registry section (satellite) ---------------------------


def test_flight_bundle_has_bounded_registry_section(tmp_path, registry):
    from geomx_tpu.telemetry.flight import FlightRecorder
    registry.counter("geomx_host_restarts_seen_total").inc(2)
    g = registry.gauge("geomx_many", labels=("i",))
    for i in range(20):
        g.labels(i=str(i)).set(float(i))
    rec = FlightRecorder(capacity=8, dump_dir=str(tmp_path),
                         min_history=1)
    rec.record(1, {"grad_norm_global": 1.0})
    rec.record(2, {"grad_norm_global": 1.1})
    fired = rec.record(3, {"grad_norm_global": float("nan")})
    assert fired and rec.dumps
    bundle = json.load(open(rec.dumps[-1]))
    reg_sec = bundle["registry"]
    assert reg_sec["geomx_host_restarts_seen_total"]["children"][0][
        "value"] == 2.0
    # bounded by the ring's size discipline (capacity children max)
    assert len(reg_sec["geomx_many"]["children"]) == 8
    assert reg_sec["geomx_many"]["dropped_children"] == 12


# ---- event-log dropped-records counter (satellite) ------------------------


def test_eventlog_rotation_counts_dropped_records(tmp_path, registry):
    from geomx_tpu.telemetry.export import EventLog
    log = EventLog(str(tmp_path / "ev.jsonl"), max_bytes=400)
    n = 0
    while log.rotations < 1:
        log.emit("e", i=n)
        n += 1
    # first rotation: there was no .1 generation yet -> nothing lost
    assert log.dropped_records == 0
    rotated_gen = EventLog._count_records(str(tmp_path / "ev.jsonl.1"))
    assert rotated_gen > 0
    while log.rotations < 2:
        log.emit("e", i=n)
        n += 1
    # the second rotation discarded the whole first .1 generation —
    # every one of its records is now counted as lost
    assert log.dropped_records == rotated_gen
    fam = registry.get("geomx_eventlog_dropped_records_total")
    assert fam is not None
    assert fam.children()[0][1].value == float(log.dropped_records)
