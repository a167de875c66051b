"""FleetScope: fleet aggregation, burn-rate determinism, freshness
provenance.

The tentpole contracts under test:

- aggregator degradation is *marked, never fatal*: a node death
  mid-poll, a torn/invalid Prometheus body, and a /healthz timeout each
  mark THAT node stale/dead with a named reason while every other
  node's folded entry stays bit-identical to a fold without the
  failure;
- the multi-window burn-rate monitor is deterministic: the same
  recorded series evaluated at the same instants yields a bit-identical
  breach list, breaches fire at onset only and re-arm after recovery;
- the gradient-to-inference propagation join keeps the earliest instant
  per (round, stage) and joins merge/publish -> apply -> first-served
  into per-round latency, per transport;
- freshness provenance fields (model_version / model_round /
  staleness_s) ride RequestLedger records, the /ledger summary, and the
  INFER_REPLY wire meta without disturbing readers that ignore them.
"""

import json
import time
import urllib.request

import numpy as np
import pytest

from geomx_tpu.control.sensors import ControlSensors
from geomx_tpu.serve.replica import ServingReplica
from geomx_tpu.service.protocol import Msg, MsgType
from geomx_tpu.telemetry.export import ledger_document, start_http_exporter
from geomx_tpu.telemetry.fleetscope import (BurnRateMonitor, FleetScope,
                                            PropagationTracker,
                                            fleetscope_from_config,
                                            get_propagation_tracker,
                                            note_propagation,
                                            parse_burn_windows,
                                            reset_propagation_tracker,
                                            roster_targets)
from geomx_tpu.telemetry.ledger import (RequestLedger, reset_request_ledger,
                                        reset_round_ledger)
from geomx_tpu.telemetry.registry import get_registry, reset_registry


@pytest.fixture(autouse=True)
def _fresh_globals():
    reset_registry()
    reset_propagation_tracker()
    yield
    reset_registry()
    reset_propagation_tracker()


# ---------------------------------------------------------------------------
# aggregator degradation: dead/stale marked with a reason, others
# bit-identical
# ---------------------------------------------------------------------------

GOOD_METRICS = "\n".join([
    '# TYPE geomx_serve_requests_total counter',
    'geomx_serve_requests_total{status="ok"} 100',
    'geomx_serve_requests_total{status="shed"} 5',
    '# TYPE geomx_wire_honesty_ratio gauge',
    'geomx_wire_honesty_ratio 1.01',
]) + "\n"

# a sample with no preceding # TYPE line: the strict parser rejects it
TORN_METRICS = "geomx_orphan_series 1\n"

GOOD_HEALTHZ = json.dumps({
    "status": "ok",
    "serving": {"v1": {"replica": {"staleness_s": 0.25}}}})

GOOD_LEDGER = json.dumps({
    "summary": {"open": 0},
    "requests": {"summary": {"qps": 50.0, "total_p50_s": 0.01,
                             "total_p99_s": 0.02}}})

PORTS = (7001, 7002, 7003)
VICTIM = 7002  # node B


def _targets(dead=()):
    return [{"name": f"serve:n{p}", "kind": "serve", "id": p,
             "host": "127.0.0.1", "port": p, "http_port": p,
             "dead": p in dead} for p in PORTS]


def _make_fetch(broken=None):
    """fetch_fn serving canned three-surface bodies per port; ``broken``
    is an optional (port, path) -> exception-or-body override."""

    def fetch(url, timeout_s):
        rest = url.split("://", 1)[1]
        hostport, _, tail = rest.partition("/")
        port = int(hostport.rsplit(":", 1)[1])
        path = "/" + tail.partition("?")[0]
        if broken is not None:
            hit = broken(port, path)
            if isinstance(hit, Exception):
                raise hit
            if hit is not None:
                return hit
        return {"/metrics": GOOD_METRICS, "/healthz": GOOD_HEALTHZ,
                "/ledger": GOOD_LEDGER}[path]

    return fetch


def _scope(targets_fn, fetch_fn):
    return FleetScope(targets_fn=targets_fn, fetch_fn=fetch_fn,
                      interval_s=1.0, stale_after_s=1.0,
                      burn_windows="60:14,300:6",
                      tracker=PropagationTracker())


def _two_polls(targets2=None, broken2=None):
    """Poll a healthy fleet at t=100, then poll again at t=110 with the
    second-tick target list / fetch overrides; return the second doc."""
    state = {"targets": _targets(), "broken": None}
    fs = _scope(lambda: state["targets"],
                _make_fetch(lambda p, path: state["broken"](p, path)
                            if state["broken"] else None))
    fs.poll_once(now=100.0)
    if targets2 is not None:
        state["targets"] = targets2
    state["broken"] = broken2
    return fs, fs.poll_once(now=110.0)


def _node_key(doc, port):
    return json.dumps(doc["nodes"][f"serve:n{port}"], sort_keys=True)


def test_degradation_marks_victim_and_leaves_others_bit_identical():
    _, control = _two_polls()
    for name, entry in control["nodes"].items():
        assert entry["health"] == "ok", (name, entry)

    scenarios = {
        "torn_metrics": dict(
            broken2=lambda p, path: TORN_METRICS
            if (p, path) == (VICTIM, "/metrics") else None,
            want_health="stale", want_reason="metrics: ValueError"),
        "healthz_timeout": dict(
            broken2=lambda p, path: TimeoutError("injected")
            if (p, path) == (VICTIM, "/healthz") else None,
            want_health="stale", want_reason="healthz: TimeoutError"),
        "death_mid_poll": dict(
            targets2=_targets(dead=(VICTIM,)),
            want_health="dead", want_reason="heartbeat_timeout"),
    }
    for label, sc in scenarios.items():
        fs, doc = _two_polls(targets2=sc.get("targets2"),
                             broken2=sc.get("broken2"))
        victim = doc["nodes"][f"serve:n{VICTIM}"]
        assert victim["health"] == sc["want_health"], (label, victim)
        assert victim["reason"] == sc["want_reason"], (label, victim)
        # marked, never fatal: the victim keeps its last-known surfaces
        assert victim["healthz"]["status"] == "ok", label
        # every OTHER node's fold is bit-identical to the no-failure fold
        for port in PORTS:
            if port == VICTIM:
                continue
            assert _node_key(doc, port) == _node_key(control, port), \
                (label, port)
        # the health flip is a named transition
        trans = [t for t in doc["transitions"]
                 if t["node"] == f"serve:n{VICTIM}"]
        assert trans and trans[-1]["to"] == sc["want_health"], label
        assert trans[-1]["reason"] == sc["want_reason"], label


def test_single_failed_poll_within_stale_window_stays_ok():
    # confidence decays from the last SUCCESSFUL poll: one failed fetch
    # a moment later must not flip the node stale while 2^(-age/T) >= .5
    state = {"broken": None}
    fs = _scope(_targets, _make_fetch(
        lambda p, path: state["broken"](p, path)
        if state["broken"] else None))
    fs.poll_once(now=100.0)
    state["broken"] = lambda p, path: TimeoutError("blip") \
        if p == VICTIM else None
    doc = fs.poll_once(now=100.5)   # age 0.5, stale_after 1.0 -> conf ~0.7
    assert doc["nodes"][f"serve:n{VICTIM}"]["health"] == "ok"
    doc = fs.poll_once(now=110.0)   # now decayed far past the knee
    assert doc["nodes"][f"serve:n{VICTIM}"]["health"] == "stale"


def test_fleet_document_shape_and_rollups():
    fs, doc = _two_polls()
    assert doc["kind"] == "geomx_fleet_document"
    assert doc["fleet_version"] == 2
    roll = doc["rollups"]
    assert roll["qps"] == pytest.approx(150.0)       # 3 nodes x 50 qps
    assert roll["request_p99_s"] == pytest.approx(0.02)
    assert roll["honesty_ratio_max"] == pytest.approx(1.01)
    assert roll["replica_staleness_max_s"] == pytest.approx(0.25)
    assert roll["shed_rate"] == pytest.approx(15.0 / 315.0)
    assert roll["nodes_ok"] == 3
    # the ControlSensors feed: rollups land in geomx_fleet_rollup{field}
    obs = ControlSensors(registry=get_registry()).observe(0)
    assert obs.fleet_qps == pytest.approx(150.0)
    assert obs.fleet_shed_rate == pytest.approx(15.0 / 315.0)
    assert obs.fleet_staleness_max_s == pytest.approx(0.25)
    assert obs.fleet_nodes_dead == 0
    # the GET /fleet body is the same document
    body, ctype = fs.document_route()
    assert ctype == "application/json"
    assert json.loads(body)["fleet_version"] == doc["fleet_version"]


def test_gxtop_renders_the_fleet_document():
    """tools/gxtop.py names every node of the document with its health
    and heads the table with the rollups' version and counts."""
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "gxtop", os.path.join(os.path.dirname(__file__), os.pardir,
                              "tools", "gxtop.py"))
    gxtop = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gxtop)
    _, doc = _two_polls()
    text = gxtop.render(doc)
    assert text.startswith("fleet v2  nodes ok/stale/dead: 3/0/0")
    for name, entry in doc["nodes"].items():
        row = next(ln for ln in text.splitlines() if ln.startswith(name))
        assert entry["health"] in row


def test_roster_targets_shapes():
    roster = {
        "serve": [(900, "127.0.0.1", 8100, "gateway"),
                  (902, "127.0.0.1", 0, "registry")],
        "worker": [(3, "10.0.0.2", 0, "p0;http=9001"),
                   (5, "10.0.0.3", 0, "")],
    }
    nodes = {n["name"]: n for n in roster_targets(roster, dead_ids=[902])}
    gw = nodes["serve:gateway"]
    assert gw["http_port"] == 8100 and not gw["dead"]
    # port 0 = binary-wire-only registration: heartbeat-covered, never
    # HTTP-polled
    reg = nodes["serve:registry"]
    assert reg["http_port"] is None and reg["dead"]
    assert nodes["worker:p0"]["http_port"] == 9001
    assert nodes["worker:5"]["http_port"] is None


def test_heartbeat_only_node_health_comes_from_dead_list():
    targets = [{"name": "serve:registry", "kind": "serve", "id": 902,
                "host": "127.0.0.1", "port": 0, "http_port": None,
                "dead": False}]
    fs = _scope(lambda: list(targets), _make_fetch())
    doc = fs.poll_once(now=100.0)
    assert doc["nodes"]["serve:registry"]["health"] == "ok"
    targets[0]["dead"] = True
    doc = fs.poll_once(now=110.0)
    assert doc["nodes"]["serve:registry"]["health"] == "dead"
    assert doc["nodes"]["serve:registry"]["reason"] == "heartbeat_timeout"


# ---------------------------------------------------------------------------
# burn-rate monitor: deterministic, onset-only, re-arming
# ---------------------------------------------------------------------------

def test_parse_burn_windows():
    assert parse_burn_windows("60:14,300:6") == ((60.0, 14.0), (300.0, 6.0))
    assert parse_burn_windows("60") == ((60.0, 1.0),)
    with pytest.raises(ValueError):
        parse_burn_windows("0:5")
    with pytest.raises(ValueError):
        parse_burn_windows("60:-1")
    with pytest.raises(ValueError):
        parse_burn_windows(" , ,")


def _burn_series():
    """A crafted two-episode series: healthy, bad burst, recovery, bad
    burst again."""
    out = []
    for t in range(0, 30):
        out.append((float(t), 9.0, 1.0))      # frac 0.1 -> burn 1.0
    for t in range(30, 45):
        out.append((float(t), 0.0, 10.0))     # all bad
    for t in range(45, 90):
        out.append((float(t), 10.0, 0.0))     # recovery
    for t in range(90, 110):
        out.append((float(t), 0.0, 10.0))     # second episode
    return out


def _run_burn(series):
    mon = BurnRateMonitor(windows="10:2,30:1", slo_target=0.9)
    breaches = []
    for t, good, bad in series:
        mon.record(t, good, bad)
        b = mon.evaluate(t)
        if b is not None:
            breaches.append(b)
    return mon, breaches


def test_burn_breach_onset_rearm_and_determinism():
    series = _burn_series()
    mon, breaches = _run_burn(series)
    # two bad episodes -> exactly two onsets, no flap storm
    assert len(breaches) == 2
    assert 30.0 <= breaches[0]["t"] < 45.0
    assert 90.0 <= breaches[1]["t"] <= 110.0
    assert breaches == mon.breaches
    for b in breaches:
        assert b["rule"] == "fleet_burn_rate"
        assert b["max_burn"] >= 2.0
        assert all(r["burn"] >= r["threshold"] for r in b["windows"])
    # each onset bumped the breach counter exactly once
    fam = get_registry().get("geomx_fleet_burn_breaches_total")
    assert fam is not None
    ((_, child),) = fam.children()
    assert child.value == 2.0
    # deterministic: the same series replayed is bit-identical
    _, again = _run_burn(series)
    assert json.dumps(breaches, sort_keys=True) == \
        json.dumps(again, sort_keys=True)


def test_burn_empty_or_healthy_series_never_breaches():
    mon = BurnRateMonitor(windows="10:2", slo_target=0.9)
    assert mon.evaluate(0.0) is None          # zero samples: no breach
    for t in range(20):
        mon.record(float(t), 10.0, 0.0)
        assert mon.evaluate(float(t)) is None
    assert mon.max_burn(19.0) == 0.0


def test_burn_requires_every_window_over_threshold():
    # short window spikes but the long window stays under: no breach
    # (the AND rule — a blip is not a page)
    mon = BurnRateMonitor(windows="5:2,60:5", slo_target=0.9)
    for t in range(0, 55):
        mon.record(float(t), 10.0, 0.0)
        assert mon.evaluate(float(t)) is None
    for t in range(55, 60):
        mon.record(float(t), 0.0, 10.0)
        assert mon.evaluate(float(t)) is None


# ---------------------------------------------------------------------------
# propagation tracker: the gradient-to-inference join
# ---------------------------------------------------------------------------

def test_propagation_join_and_min_instant():
    tr = PropagationTracker()
    tr.note(7, "publish", t=10.0)
    tr.note(7, "apply", t=10.5)
    tr.note(7, "served", t=11.0, transport="http")
    (rec,) = tr.rounds()
    assert rec["propagation_s"] == pytest.approx(1.0)   # publish fallback
    # a merge instant learned later re-anchors the span
    tr.note(7, "merge", t=9.0)
    (rec,) = tr.rounds()
    assert rec["propagation_s"] == pytest.approx(2.0)
    # served keeps the EARLIEST instant, per transport too
    tr.note(7, "served", t=10.8, transport="native")
    (rec,) = tr.rounds()
    assert rec["served"] == pytest.approx(10.8)
    assert rec["served_by"] == {"http": pytest.approx(11.0),
                                "native": pytest.approx(10.8)}
    s = tr.summary()
    assert s["rounds_completed"] == 1
    assert s["p50_s"] == pytest.approx(1.8)
    assert s["by_transport"] == {"http": 1, "native": 1}


def test_propagation_bounds_and_errors():
    tr = PropagationTracker(capacity=2)
    for rid in (1, 2, 3):
        tr.note(rid, "publish", t=float(rid))
    assert [r["round"] for r in tr.rounds()] == [2, 3]   # FIFO bound
    tr.note(0, "publish", t=1.0)                          # ignored
    assert len(tr.rounds()) == 2
    with pytest.raises(ValueError):
        tr.note(5, "warp", t=1.0)
    with pytest.raises(ValueError):
        note_propagation(5, "warp")


def test_propagation_ingest_round_records():
    tr = PropagationTracker()
    n = tr.ingest_round_records([
        {"round": 6, "hops": [{"hop": "push", "t": 1.0},
                              {"hop": "journal", "t": 49.0},
                              {"hop": "merge", "t": 50.0}]},
        {"round": 0, "hops": [{"hop": "merge", "t": 1.0}]},   # ignored
        {"no_round": True},
    ])
    assert n == 1
    (rec,) = tr.rounds()
    assert rec["round"] == 6 and rec["merge"] == pytest.approx(49.0)


def test_propagation_publishes_histogram_on_completion():
    tr = get_propagation_tracker()
    tr.note(3, "merge", t=1.0)
    tr.note(3, "served", t=1.5, transport="http")
    fam = get_registry().get("geomx_fleet_propagation_seconds")
    assert fam is not None
    ((_, child),) = fam.children()
    _cum, total, count = child.snapshot()
    assert count == 1 and total == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# freshness provenance: ledger records, summaries, wire meta
# ---------------------------------------------------------------------------

def _observe(led, rid, **kw):
    led.observe(rid, t_enqueue=float(rid), queue_s=0.001,
                forward_s=0.002, reply_s=0.0005, batch_size=1,
                bucket=1, **kw)


def test_request_ledger_provenance_fields_and_summary():
    led = RequestLedger(capacity=8)
    _observe(led, 1, transport="http", model_version="v1",
             model_round=7, staleness_s=0.5)
    _observe(led, 2, transport="native", model_version="v1",
             model_round=9, staleness_s=0.1)
    _observe(led, 3)   # a record without provenance stays untouched
    recs = led.records()
    assert recs[0]["model_version"] == "v1"
    assert recs[0]["model_round"] == 7
    assert recs[0]["staleness_s"] == pytest.approx(0.5)
    assert "model_round" not in recs[2]
    fresh = led.summary()["freshness"]
    assert fresh == {"records": 2, "model_round_min": 7,
                     "model_round_max": 9,
                     "staleness_max_s": pytest.approx(0.5)}


def test_infer_reply_provenance_wire_safe():
    arr = np.arange(6, dtype=np.float32).reshape(2, 3)
    meta = {"rid": 3, "status": "ok", "model_version": "v1",
            "model_round": 7, "staleness_s": 0.125,
            "layer_rounds": {"w0": 7, "w1": 6}}
    out = Msg.decode(Msg(MsgType.INFER_REPLY, key="infer", sender=1,
                         meta=dict(meta), array=arr).encode())
    assert out.type == MsgType.INFER_REPLY
    assert dict(out.meta) == meta
    assert np.array_equal(out.array, arr)
    # mixed fleet: a reply WITHOUT the provenance keys decodes exactly
    # as before — the keys are additive, never required
    old_meta = {"rid": 3, "status": "ok"}
    out = Msg.decode(Msg(MsgType.INFER_REPLY, key="infer", sender=1,
                         meta=dict(old_meta), array=arr).encode())
    assert dict(out.meta) == old_meta
    assert np.array_equal(out.array, arr)


def test_replica_publishes_layer_round_watermarks():
    rep = ServingReplica("v1")
    rep.install_base("w0", np.zeros(4, np.float32), 0)
    assert rep.apply_delta("w0", 3, np.array([1.5], np.float32),
                           np.array([0], np.int64))
    assert rep.layer_rounds() == {"w0": 3}
    assert rep.snapshot()["layer_rounds"] == {"w0": 3}
    fam = get_registry().get("geomx_serve_replica_round")
    assert fam is not None
    vals = {lv[0]: child.value for lv, child in fam.children()}
    assert vals == {"w0": 3.0}
    # the apply hop landed in the propagation join
    (rec,) = get_propagation_tracker().rounds()
    assert rec["round"] == 3 and "apply" in rec


# ---------------------------------------------------------------------------
# /ledger query modes (summary=1 / n=K) on the shared exporter
# ---------------------------------------------------------------------------

def test_ledger_document_summary_and_bounded_modes():
    reset_round_ledger()
    led = reset_request_ledger(capacity=8)
    for rid in (1, 2, 3):
        _observe(led, rid, model_round=rid)
    full = ledger_document()
    assert len(full["requests"]["records"]) == 3
    assert "records" in full
    brief = ledger_document(summary_only=True)
    assert "records" not in brief
    assert "records" not in brief["requests"]
    assert brief["requests"]["summary"]["freshness"]["records"] == 3
    bounded = ledger_document(max_records=2)
    assert len(bounded["requests"]["records"]) == 2
    assert [r["rid"] for r in bounded["requests"]["records"]] == [2, 3]
    reset_request_ledger()
    reset_round_ledger()


def test_ledger_http_route_query_modes():
    reset_round_ledger()
    led = reset_request_ledger(capacity=8)
    for rid in (1, 2, 3):
        _observe(led, rid)
    srv = start_http_exporter("127.0.0.1", 0)
    port = srv.server_address[1]
    try:
        def get(path):
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}{path}", timeout=5) as r:
                return json.loads(r.read().decode("utf-8"))

        assert len(get("/ledger")["requests"]["records"]) == 3
        brief = get("/ledger?summary=1")
        assert "records" not in brief["requests"]
        assert len(get("/ledger?n=1")["requests"]["records"]) == 1
        assert len(get("/ledger?n=bogus")["requests"]["records"]) == 3
    finally:
        srv.shutdown()
        srv.server_close()
        reset_request_ledger()
        reset_round_ledger()


# ---------------------------------------------------------------------------
# serve-role roster registration: a dead gateway is a NAMED death
# ---------------------------------------------------------------------------

def test_serve_registration_and_named_death():
    from geomx_tpu.service.scheduler import GeoScheduler, SchedulerClient
    sched = GeoScheduler(port=0, heartbeat_timeout=0.6)
    sched.start()
    cli = None
    try:
        cli = SchedulerClient(("127.0.0.1", sched.port))
        cli.register("serve", port=8123, tag="gateway")
        cli.heartbeat()
        snap = sched.health_snapshot()
        assert snap["roster"].get("serve") == 1
        assert snap["dead_nodes"] == []
        # stop heartbeating; the gateway must die BY NAME
        deadline = time.monotonic() + 10.0
        dead = []
        while time.monotonic() < deadline:
            dead = sched.health_snapshot()["dead_nodes"]
            if dead:
                break
            time.sleep(0.1)
        assert dead, "gateway never declared dead"
        assert dead[0]["role"] == "serve" and dead[0]["tag"] == "gateway"
        assert dead[0]["id"] == cli.node_id
    finally:
        if cli is not None:
            cli.close()
        sched.stop()


def test_fleetscope_from_config_gating(monkeypatch):
    for var in ("GEOMX_FLEETSCOPE", "GEOMX_FLEETSCOPE_INTERVAL_S",
                "GEOMX_FLEETSCOPE_BURN_WINDOWS"):
        monkeypatch.delenv(var, raising=False)
    sentinel = object()
    assert fleetscope_from_config(sentinel) is None   # default: off
    monkeypatch.setenv("GEOMX_FLEETSCOPE", "1")
    monkeypatch.setenv("GEOMX_FLEETSCOPE_INTERVAL_S", "0.5")
    monkeypatch.setenv("GEOMX_FLEETSCOPE_BURN_WINDOWS", "30:2")
    fs = fleetscope_from_config(sentinel)
    assert isinstance(fs, FleetScope)
    assert fs.interval_s == pytest.approx(0.5)
    assert fs.burn.windows == ((30.0, 2.0),)
    assert fs.scheduler is sentinel
