"""Fleet round ledger (telemetry/ledger.py, docs/telemetry.md "Round
ledger"): causal per-round hop chains, byte-true wire accounting at
the Msg.encode/decode choke point, bounded memory, the observability
satellites (server HTTP surface, redirect/retry accounting, resend
buffer audit), and the flight-recorder / link-observatory feeds.

These tests pin the mechanisms at 1-2 workers in seconds.
"""

import bisect
import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from geomx_tpu.service import (GeoPSClient, GeoPSServer, GeoScheduler,
                               SchedulerClient, ShardedGlobalClient,
                               start_sharded_global_tier)
from geomx_tpu.service.protocol import Msg, MsgType
from geomx_tpu.service.shardmap import even_bounds, key_hash
from geomx_tpu.telemetry import get_registry
from geomx_tpu.telemetry.ledger import (FRAME_OVERHEAD_BOUND, RoundLedger,
                                        get_round_ledger,
                                        reset_round_ledger)


@pytest.fixture()
def ledger():
    led = reset_round_ledger(capacity=512)
    yield led
    reset_round_ledger()


def _retry_count(op: str) -> float:
    fam = get_registry().get("geomx_rpc_retries_total")
    if fam is None:
        return 0.0
    return dict(fam.children()).get((op,), None).value \
        if (op,) in dict(fam.children()) else 0.0


# ---- RoundLedger unit -----------------------------------------------------


def test_record_hops_complete_and_snapshot(ledger):
    ledger.record_hop("w", 1, "push", party=3, nbytes=100)
    ledger.record_hop("w", 1, "merge", shard=2, dur_s=0.01)
    ledger.record_hop("w", 1, "reply", party=3)
    ledger.add_phase("w", 1, "merge", 0.01)
    rec = ledger.get("w", 1)
    assert rec["status"] == "open"
    assert [h["seq"] for h in rec["hops"]] == [0, 1, 2]
    assert rec["origin_party"] == 3
    ledger.complete("w", 1)
    rec = ledger.get("w", 1)
    assert rec["status"] == "complete" and rec["closed_unix"] is not None
    assert rec["phases"] == {"merge": 0.01}
    # late reply hops still append to the completed record (pulls of a
    # round legitimately arrive after its merge)
    ledger.record_hop("w", 1, "reply", party=4)
    assert [h["hop"] for h in ledger.get("w", 1)["hops"]][-1] == "reply"
    # completing twice is a no-op
    ledger.complete("w", 1)
    assert ledger.completed_total == 1


def test_completed_records_evict_fifo_with_counter():
    led = RoundLedger(capacity=4)
    for r in range(1, 8):
        led.record_hop("w", r, "merge")
        led.complete("w", r)
    assert led.completed_total == 7
    assert led.evicted_total == 3
    kept = [(r["key"], r["round"]) for r in led.records()]
    assert kept == [("w", 4), ("w", 5), ("w", 6), ("w", 7)]


def test_open_rounds_bounded_by_orphaning():
    """A client-only process (no server completes its rounds) must not
    leak one open record per push: past the open capacity the oldest
    open round closes as status=orphaned."""
    led = RoundLedger(capacity=8, open_capacity=4)
    for r in range(1, 7):
        led.record_hop("w", r, "push", party=0)
    stats = {r["status"] for r in led.records()}
    assert "orphaned" in stats
    assert led.orphaned_total == 2
    orphans = [r for r in led.records() if r["status"] == "orphaned"]
    assert {(r["key"], r["round"]) for r in orphans} == \
        {("w", 1), ("w", 2)}
    assert orphans[0]["detail"]["close_reason"] == "open_capacity"


def test_straggler_hops_do_not_resurrect_evicted_rounds():
    """A reply hop / reply bytes for a round already FIFO-evicted must
    not re-create it as a fresh open record that nothing will ever
    complete (it would age the stuck-round signal and eventually count
    a clean round as orphaned); only push frames may open records."""
    led = RoundLedger(capacity=2)
    for r in (1, 2, 3):
        led.record_hop("w", r, "merge")
        led.complete("w", r)
    assert led.get("w", 1) is None           # evicted
    led.record_hop("w", 1, "reply", party=0)
    led.record_hop("w", 1, "journal")
    led.add_phase("w", 1, "reply", 0.1)
    led.account_frame("rx", "PULL_REPLY", "w", 1, nbytes=100)
    assert led.get("w", 1) is None           # stayed gone
    led.account_frame("rx", "PUSH", "w", 9, nbytes=100)
    assert led.get("w", 9)["status"] == "open"   # pushes still open


def test_complete_through_closes_client_side_rounds():
    """The worker-process completion path: a pull reply's ``pushed``
    proof closes every open round of the key it covers (a client-side
    ledger never sees the server's merge)."""
    led = RoundLedger(capacity=8)
    for r in (1, 2, 3):
        led.record_hop("k", r, "push", party=0)
    assert led.complete_through("k", 2) == 2
    assert led.get("k", 1)["status"] == "complete"
    assert led.get("k", 2)["status"] == "complete"
    assert led.get("k", 3)["status"] == "open"
    assert led.complete_through("k", 2) == 0     # idempotent


def test_orphan_api_closes_matching_open_rounds():
    led = RoundLedger(capacity=8)
    led.record_hop("a", 1, "push")
    led.record_hop("a", 2, "push")
    led.record_hop("b", 1, "push")
    assert led.orphan(key="a", reason="relay_failed") == 2
    assert led.get("a", 1)["status"] == "orphaned"
    assert led.get("a", 1)["detail"]["close_reason"] == "relay_failed"
    assert led.get("b", 1)["status"] == "open"


def test_summary_scalars_deterministic_now():
    led = RoundLedger(capacity=8)
    led.record_hop("w", 1, "push")
    t0 = led.get("w", 1)["opened_unix"]
    s = led.summary(now=t0 + 12.5)
    assert s["ledger_open_rounds"] == 1
    assert s["ledger_open_round_age_s"] == pytest.approx(12.5)
    assert s["ledger_oldest_open"] == ("w", 1)


# ---- byte accounting at the encode/decode choke point ---------------------


def test_account_frame_via_encode_decode(ledger):
    g = np.ones(128, np.float32)
    msg = Msg(MsgType.PUSH, key="w", sender=5,
              meta={"round": 3, "wire_declared": int(g.nbytes)}, array=g)
    frame = msg.encode()
    Msg.decode(frame)
    rec = ledger.get("w", 3)
    assert rec["wire"]["push_tx_frames"] == 1
    assert rec["wire"]["push_tx_bytes"] == len(frame) + 4
    assert rec["wire"]["push_rx_bytes"] == len(frame) + 4
    assert rec["declared_tx_bytes"] == g.nbytes
    assert rec["declared_rx_bytes"] == g.nbytes
    # the honesty ratio covers framing only: payload <= frame <=
    # payload + the documented per-frame bound
    assert 1.0 <= rec["honesty_ratio"] \
        <= 1.0 + FRAME_OVERHEAD_BOUND / g.nbytes
    # a RE-DELIVERY decodes again (retry overhead is visible on the
    # receive side) while the encode side counted once
    Msg.decode(frame)
    rec = ledger.get("w", 3)
    assert rec["wire"]["push_rx_frames"] == 2
    assert rec["wire"]["push_tx_frames"] == 1


def test_frames_without_round_or_key_not_accounted(ledger):
    Msg(MsgType.ACK, key="w").encode()
    Msg(MsgType.PUSH, key=None, meta={"round": 1}).encode()
    Msg(MsgType.COMMAND, key="w", meta={"round": 1,
                                        "cmd": "hello"}).encode()
    assert ledger.records() == []


def test_reconciles_flags_undeclared_overhead():
    led = RoundLedger(capacity=8)
    led.account_frame("rx", "PUSH", "w", 1, nbytes=1000, declared=900)
    rec = [r for r in led.records()][0]
    assert 900 <= 1000 <= 900 + FRAME_OVERHEAD_BOUND * 1
    # a frame whose measured bytes exceed declared + bound fails
    led2 = RoundLedger(capacity=8)
    led2.account_frame("rx", "PUSH", "w", 1, nbytes=2000, declared=900)
    recs = {(r["key"], r["round"]): r for r in led2.records()}
    from geomx_tpu.telemetry.ledger import RoundRecord
    rr = RoundRecord("w", 1)
    rr.wire.update({"push_rx_bytes": 2000, "push_rx_frames": 1})
    rr.declared_rx = 900
    assert not rr.reconciles()
    rr2 = RoundRecord("w", 1)
    rr2.wire.update({"push_rx_bytes": 1000, "push_rx_frames": 1})
    rr2.declared_rx = 900
    assert rr2.reconciles()
    assert recs  # the account_frame path built a record


# ---- end-to-end: one sync round through a real server ---------------------


def test_round_gapless_end_to_end(ledger, tmp_path):
    srv = GeoPSServer(num_workers=2, mode="sync", accumulate=True,
                      durable_dir=str(tmp_path),
                      durable_name="led").start()
    c0 = GeoPSClient(("127.0.0.1", srv.port), sender_id=0)
    c1 = GeoPSClient(("127.0.0.1", srv.port), sender_id=1)
    try:
        c0.init("w", np.zeros(64, np.float32))
        c0.push("w", np.ones(64, np.float32))
        c1.push("w", np.ones(64, np.float32))
        assert np.allclose(c0.pull("w"), 2.0)
        assert np.allclose(c1.pull("w"), 2.0)
        rec = ledger.get("w", 1)
        kinds = [h["hop"] for h in rec["hops"]]
        assert rec["status"] == "complete"
        assert kinds.count("push") == 2
        assert kinds.count("merge") == 1
        assert "journal" in kinds                  # durable server
        assert kinds.count("reply") >= 2
        assert [h["seq"] for h in rec["hops"]] == \
            list(range(len(rec["hops"])))
        # phases recorded AND observed into the per-shard histogram
        assert {"gate_wait", "merge", "journal", "reply"} <= \
            set(rec["phases"])
        fam = get_registry().get("geomx_round_phase_seconds")
        assert fam is not None
        phases = {lbl[1] for lbl, ch in fam.children() if ch.count > 0}
        assert {"gate_wait", "merge", "reply"} <= phases
        # byte-true reconciliation: declared payload covered exactly
        # once plus bounded framing overhead
        assert rec["declared_rx_bytes"] == 2 * 64 * 4
        measured = rec["wire"]["push_rx_bytes"]
        assert rec["declared_rx_bytes"] <= measured <= \
            rec["declared_rx_bytes"] + \
            FRAME_OVERHEAD_BOUND * rec["wire"]["push_rx_frames"]
    finally:
        c0.close()
        c1.close()
        srv.stop(forward=False)


def test_p3_chunked_push_one_hop_per_chunk(ledger):
    srv = GeoPSServer(num_workers=1, mode="sync", accumulate=True).start()
    c = GeoPSClient(("127.0.0.1", srv.port), sender_id=0,
                    p3_slice_elems=16)
    try:
        c.init("w", np.zeros(100, np.float32))
        c.push("w", np.ones(100, np.float32))
        np.allclose(c.pull("w"), 1.0)
        rec = ledger.get("w", 1)
        pushes = [h for h in rec["hops"] if h["hop"] == "push"]
        assert len(pushes) == 7                   # ceil(100/16) chunks
        assert sorted(h["detail"]["chunk"] for h in pushes) == \
            list(range(7))
        # per-chunk declared bytes sum to the whole tensor
        assert rec["declared_rx_bytes"] == 100 * 4
    finally:
        c.close()
        srv.stop(forward=False)


# ---- satellite: server HTTP /metrics + /healthz + /ledger -----------------


def test_server_http_surface(ledger):
    srv = GeoPSServer(num_workers=1, mode="sync", accumulate=True,
                      metrics_port=0).start()
    c = GeoPSClient(("127.0.0.1", srv.port), sender_id=0)
    try:
        assert srv.metrics_port
        c.init("w", np.zeros(8, np.float32))
        c.push("w", np.ones(8, np.float32))
        c.pull("w")
        base = f"http://127.0.0.1:{srv.metrics_port}"
        text = urllib.request.urlopen(base + "/metrics",
                                      timeout=5).read().decode()
        from geomx_tpu.telemetry import parse_prometheus_text
        fams = parse_prometheus_text(text)
        assert "geomx_server_pushes_total" in fams
        assert "geomx_ledger_rounds_total" in fams
        health = json.loads(urllib.request.urlopen(
            base + "/healthz", timeout=5).read())
        assert health["role"] == "ps_server"
        assert health["num_workers"] == 1 and health["num_keys"] == 1
        led = json.loads(urllib.request.urlopen(
            base + "/ledger", timeout=5).read())
        assert any(r["key"] == "w" and r["round"] == 1
                   for r in led["records"])
        assert led["summary"]["ledger_completed_total"] >= 1
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(base + "/nope", timeout=5)
    finally:
        c.close()
        srv.stop(forward=False)
        assert srv._metrics_srv is None   # stop closed the exporter


def test_server_metrics_port_env_zero_disables(monkeypatch):
    monkeypatch.setenv("GEOMX_SERVER_METRICS_PORT", "0")
    srv = GeoPSServer(num_workers=1).start()
    try:
        assert srv.metrics_port is None
    finally:
        srv.stop(forward=False)


# ---- satellite: redirect observability under rebalance --------------------


def test_redirect_counts_one_retry_and_ledger_hop(ledger):
    """A mid-round wrong_shard redirect increments exactly one
    geomx_rpc_retries_total{op="redirect"}, leaves a redirect hop in
    the round's ledger record, and double-counts no socket bytes (the
    wire totals equal the sum of the per-frame push hops — the
    redirected attempt and the re-route each counted exactly once)."""
    sched = GeoScheduler().start()
    servers = start_sharded_global_tier(("127.0.0.1", sched.port),
                                        num_shards=2, num_workers=1)
    w = ShardedGlobalClient(("127.0.0.1", sched.port), sender_id=0)
    sc = SchedulerClient(("127.0.0.1", sched.port))
    try:
        from geomx_tpu.service.shardmap import ShardMap
        m = ShardMap.from_meta(sc.shard_map())
        hot = [k for k in (f"h{i}" for i in range(64))
               if m.shard_for(k) == 0][:4]
        cold = [k for k in (f"c{i}" for i in range(64))
               if m.shard_for(k) == 1][:1]
        for k in hot + cold:
            w.init(k, np.zeros(16, np.float32))
        for _r in range(3):                      # skew the load
            for k in hot:
                w.push(k, np.ones(16, np.float32))
                w.pull(k)
        for k in cold:
            w.push(k, np.ones(16, np.float32))
            w.pull(k)
        res = sc.rebalance_shards(min_gain=0.05)
        assert res["changed"]
        m2 = ShardMap.from_meta(res["map"])
        moved = next(k for k in hot if m2.shard_for(k) != 0)
        before = _retry_count("redirect")
        w.push(moved, np.ones(16, np.float32))   # stale map -> redirect
        after = _retry_count("redirect")
        assert after - before == 1
        rnd = w._rounds[moved]
        rec = ledger.get(moved, rnd)
        redirects = [h for h in rec["hops"] if h["hop"] == "redirect"]
        assert len(redirects) == 1
        assert redirects[0]["shard"] == 0        # the refusing shard
        assert redirects[0]["detail"]["map_version"] >= 2
        # no double-counted socket bytes: the round's tx total equals
        # the per-frame push hops (redirected attempt + re-route)
        pushes = [h for h in rec["hops"] if h["hop"] == "push"]
        assert len(pushes) == 2
        assert rec["wire"]["push_tx_frames"] == 2
        assert rec["wire"]["push_tx_bytes"] == \
            sum(h["nbytes"] for h in pushes)
        w.pull(moved)                             # round completes
        assert ledger.get(moved, rnd)["status"] == "complete"
    finally:
        sc.close()
        w.close()
        for srv in servers:
            srv.stop(forward=False)
        sched.stop()


# ---- satellite: resend-buffer audit across failover re-join ---------------


def test_resend_buffer_zero_after_failover_rejoin(ledger, tmp_path):
    """geomx_resend_buffer_bytes{sender} must return to ZERO once a
    failover re-join completes and its rounds' pulls are consumed —
    both retention layers (the per-shard client's frame set and the
    wrapper's failover copy) release on the pull-reply proof."""
    bounds = even_bounds(2)
    key = next(k for k in (f"p{i}" for i in range(256))
               if bisect.bisect_right(bounds, key_hash(k)) - 1 == 1)
    sched = GeoScheduler(durable_dir=str(tmp_path / "sched")).start()
    addr = ("127.0.0.1", sched.port)
    tier = str(tmp_path / "tier")
    servers = start_sharded_global_tier(addr, num_shards=2,
                                        num_workers=2,
                                        durable_dir=tier)
    w = ShardedGlobalClient(addr, sender_id=4242, reconnect=True,
                            p3_slice_elems=32,
                            reconnect_timeout_s=3.0, op_timeout_s=60.0)
    w2 = ShardedGlobalClient(addr, sender_id=4243, reconnect=True,
                             p3_slice_elems=32,
                             reconnect_timeout_s=3.0, op_timeout_s=60.0)
    repl = None
    try:
        fam = get_registry().get("geomx_resend_buffer_bytes")

        def gauge():
            ch = dict(fam.children()).get(("4242",))
            return 0.0 if ch is None else ch.value

        for c in (w, w2):
            c.init(key, np.zeros(64, np.float32))
        w.push(key, np.ones(64, np.float32))
        assert gauge() > 0                       # retained in flight
        w2.push(key, np.ones(64, np.float32))
        w.pull(key, timeout=30.0)
        w2.pull(key, timeout=30.0)
        deadline = time.monotonic() + 5.0
        while gauge() != 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert gauge() == 0                      # clean-path release
        w.push(key, np.ones(64, np.float32))     # round 2 OPEN (1/2)
        assert gauge() > 0
        old_port = servers[1].port
        servers[1].crash()                       # round 2 lost
        repl = GeoPSServer(num_workers=2, mode="sync", accumulate=True,
                           rank=1, shard_index=1, port=0,
                           shard_range=(bounds[1], bounds[2]),
                           shard_map_version=1, durable_dir=tier,
                           durable_name="shard1").start()
        assert repl.port != old_port
        sc = SchedulerClient(addr)
        try:
            sc.shard_failover(1, "127.0.0.1", repl.port)
        finally:
            sc.close()
        done = []

        def other():
            w2.push(key, np.ones(64, np.float32))
            done.append(True)

        t = threading.Thread(target=other, daemon=True)
        t.start()
        val = w.pull(key, timeout=60.0)          # forces the re-join
        t.join(30.0)
        assert done and np.allclose(val, 4.0)
        deadline = time.monotonic() + 5.0
        while gauge() != 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert gauge() == 0, \
            "resend buffer leaked across the failover re-join"
        # ...and the ledger shows the failover attribution
        rec = ledger.get(key, 2)
        assert any(h["hop"] == "failover_replay" and h["shard"] == 1
                   for h in rec["hops"])
        assert rec["status"] == "complete"
    finally:
        w.close()
        w2.close()
        for s in [servers[0], repl]:
            if s is not None:
                s.stop(forward=False)
        sched.stop()


# ---- session-resume ordering: pull-during-outage sees the replay ----------


def test_inplace_restart_replay_happens_before_queued_pull(ledger,
                                                           tmp_path):
    """A pull submitted during the outage must NOT overtake the resume
    replay it depends on (the replays direct-send on the fresh socket
    before the queue drains): the pull parks until the replayed round
    completes instead of reading pre-crash state."""
    srv = GeoPSServer(num_workers=2, mode="sync", accumulate=True,
                      durable_dir=str(tmp_path),
                      durable_name="g").start()
    port = srv.port
    ca = GeoPSClient(("127.0.0.1", port), sender_id=0, reconnect=True,
                     p3_slice_elems=32)
    cb = GeoPSClient(("127.0.0.1", port), sender_id=1, reconnect=True,
                     p3_slice_elems=32)
    srv2 = None
    try:
        for c in (ca, cb):
            c.init("w", np.zeros(64, np.float32))
        ca.push("w", np.ones(64, np.float32))
        cb.push("w", np.ones(64, np.float32))
        assert np.allclose(ca.pull("w"), 2.0)
        assert np.allclose(cb.pull("w"), 2.0)
        ca.push("w", np.full(64, 5.0, np.float32))   # round 2 OPEN
        time.sleep(0.2)
        srv.crash()                                  # round 2 lost
        # the pull is QUEUED while the server is down; the replayed
        # push must still reach the restarted server first
        got = []

        def puller():
            got.append(ca.pull("w", timeout=30.0))

        t = threading.Thread(target=puller, daemon=True)
        t.start()
        time.sleep(0.1)
        srv2 = GeoPSServer(num_workers=2, mode="sync", accumulate=True,
                           port=port, durable_dir=str(tmp_path),
                           durable_name="g").start()
        cb.push("w", np.ones(64, np.float32))
        t.join(30.0)
        assert got and np.allclose(got[0], 8.0), \
            "pull overtook the session-resume replay and read stale " \
            "state"
        rec = ledger.get("w", 2)
        assert rec["status"] == "complete"
        assert any(h["hop"] == "replay" for h in rec["hops"])
    finally:
        for c in (ca, cb):
            c.close()
        for s in (srv, srv2):
            if s is not None:
                try:
                    s.stop(forward=False)
                except Exception:
                    pass


# ---- flight recorder rules ------------------------------------------------


def test_flight_stuck_round_rule_fires():
    from geomx_tpu.telemetry.flight import STUCK_ROUND, FlightRecorder
    led = RoundLedger(capacity=8)
    led.record_hop("w", 1, "push")
    t0 = led.get("w", 1)["opened_unix"]
    fr = FlightRecorder(capacity=16, stuck_round_s=30.0)
    assert fr.record_ledger(1, ledger=led, now=t0 + 5.0) == []
    fired = fr.record_ledger(2, ledger=led, now=t0 + 31.0)
    assert [f["rule"] for f in fired] == [STUCK_ROUND]
    assert fired[0]["oldest_open"] == ("w", 1)


def test_flight_honesty_drift_rule_fires_deterministically():
    from geomx_tpu.telemetry.flight import HONESTY_DRIFT, FlightRecorder
    fr = FlightRecorder(capacity=64, honesty_drift=0.25, min_history=5)
    for s in range(8):
        assert fr.record(s, {"wire_honesty_ratio": 1.1}) == []
    fired = fr.record(8, {"wire_honesty_ratio": 1.6})
    assert [f["rule"] for f in fired] == [HONESTY_DRIFT]
    assert fired[0]["rolling_median"] == pytest.approx(1.1)
    # same sequence, same firing (pure function of the ring)
    fr2 = FlightRecorder(capacity=64, honesty_drift=0.25, min_history=5)
    for s in range(8):
        fr2.record(s, {"wire_honesty_ratio": 1.1})
    assert [f["rule"] for f in fr2.record(8,
            {"wire_honesty_ratio": 1.6})] == [HONESTY_DRIFT]


# ---- observatory feeds ----------------------------------------------------


def test_ingest_ledger_builds_link_estimates():
    from geomx_tpu.telemetry.links import LinkObservatory
    led = RoundLedger(capacity=16)
    t0 = 1_000_000.0
    for party in (0, 1):
        led.record_hop("w", 1, "push", party=party, nbytes=4096,
                       t=t0 + party * 0.01)
    led.record_hop("w", 1, "merge", shard=0, t=t0 + 0.1)
    led.complete("w", 1)
    led.record_hop("x", 1, "push", party=2, nbytes=100, t=t0)
    led.orphan(key="x", reason="relay_failed")
    obs = LinkObservatory()
    folded = obs.ingest_ledger(led.records())
    assert folded >= 3
    snap = obs.snapshot(now=t0 + 1.0)
    assert "party0->global" in snap and "party1->global" in snap
    assert snap["party0->global"]["throughput_bps"] > 0
    assert snap["party2->global"]["loss_rate"] > 0
    # deterministic: same records, same snapshot
    obs2 = LinkObservatory()
    obs2.ingest_ledger(led.records())
    assert obs2.snapshot(now=t0 + 1.0) == snap


def test_ledger_to_doc_merges_into_round_linked_trace():
    from geomx_tpu.telemetry import merge_traces, rounds_in_trace
    led = RoundLedger(capacity=16)
    for r in (1, 2):
        led.record_hop("w", r, "push", party=0, nbytes=64)
        led.record_hop("w", r, "merge", shard=1)
        led.record_hop("w", r, "reply", party=0)
        led.complete("w", r)
    doc = led.to_doc(label="test-ledger")
    assert doc["metadata"]["anchor_unix_us"] > 0
    merged = merge_traces([doc], labels=["ledger"])
    linked = rounds_in_trace(merged)
    assert ("w", 1) in linked and ("w", 2) in linked
    assert all(len(evs) >= 3 for evs in linked.values())
