"""Geo-distributed serving plane (geomx_tpu/serve/, docs/serving.md).

The contracts under test:

- registry: base + sparse pair deltas reconstruct bit-exactly vs a
  dense checkpoint maintained with the same add semantics; a replayed
  delta dedups on BOTH (layer, round) and (sender, rid) — add
  semantics make double-apply silent corruption, so idempotence is
  load-bearing; a torn journal tail truncates and replays clean; the
  persisted generation token bumps per restart so replicas detect it;
- refresh ordering: the pending plan is P3-style — base frames in
  publish order first, then deltas layer-major (early layers before
  late ones), rounds ascending within a layer;
- gateway: continuous batching pads to power-of-two buckets so the
  jit cache stays bounded at len(buckets) per input shape; the
  request ledger attributes queue/forward/reply phases with p50/p99;
- surfaces: /healthz grows a serving section, the three
  geomx_serve_* metrics export, and the SloPolicy sheds with
  hysteresis like every other pilot family;
- overhead: the GEOMX_SERVE_* knobs are host-plane only — the traced
  train step stays byte-identical with serving configured.
"""

import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

from geomx_tpu.config import GeoConfig
from geomx_tpu.control.policy import GraftPilot, SloPolicy
from geomx_tpu.control.sensors import ControlObservation
from geomx_tpu.serve import (register_serving_surface,
                             reset_serving_surface, serving_surface)
from geomx_tpu.serve.gateway import InferenceGateway, default_buckets
from geomx_tpu.serve.registry import (ModelRegistry, RegistryClient,
                                      RegistryServer)
from geomx_tpu.serve.replica import ServingReplica
from geomx_tpu.telemetry.ledger import (REQUEST_PHASES, RequestLedger,
                                        get_round_ledger,
                                        reset_request_ledger,
                                        reset_round_ledger)


# --------------------------------------------------------------------------
# registry core
# --------------------------------------------------------------------------

def _publish_with_deltas(reg, rng, version="v1", rounds=3, layers=2,
                         dims=(12, 5)):
    params = {f"{i:04d}/layer{i}": rng.normal(size=(dims[i % len(dims)],))
              .astype(np.float32) for i in range(layers)}
    reg.publish(version, params)
    dense = {k: v.copy() for k, v in params.items()}
    for r in range(1, rounds + 1):
        for k in params:
            n = dense[k].size
            idx = rng.choice(n, size=max(1, n // 3),
                             replace=False).astype(np.int64)
            vals = rng.normal(size=idx.size).astype(np.float32)
            assert reg.apply_delta(version, k, r, vals, idx,
                                   sender=1, rid=f"{r}/{k}")
            np.add.at(dense[k].reshape(-1), idx, vals)
    return params, dense


def test_base_plus_delta_reconstruction_bit_exact():
    """materialize() == a dense checkpoint maintained with the same
    np.add.at adds — bit-exact, not allclose: same order, same dtype,
    same accumulation."""
    rng = np.random.default_rng(0)
    reg = ModelRegistry()
    params, dense = _publish_with_deltas(reg, rng, rounds=4)
    mat = reg.materialize("v1")
    for k in params:
        assert np.array_equal(mat[k], dense[k]), k


def test_delta_apply_idempotent_both_dedup_keys():
    """A replayed push must not double-apply: the (layer, round) pair
    rejects a re-push of an applied round, and the (sender, rid) pair
    rejects a session-resume replay even under a NEW round id."""
    rng = np.random.default_rng(1)
    reg = ModelRegistry()
    params, dense = _publish_with_deltas(reg, rng, rounds=2)
    k = next(iter(params))
    vals = np.ones(2, np.float32)
    idx = np.array([0, 1], np.int64)
    before = reg.materialize("v1")

    # same (layer, round), fresh rid -> dedup
    assert reg.apply_delta("v1", k, 2, vals, idx,
                           sender=1, rid="fresh") is False
    # same (sender, rid), new round -> dedup
    assert reg.apply_delta("v1", k, 99, vals, idx,
                           sender=1, rid=f"2/{k}") is False
    assert reg.replays_deduped == 2
    after = reg.materialize("v1")
    for name in params:
        assert np.array_equal(before[name], after[name]), name


def test_pending_plan_is_early_layer_first():
    """P3 refresh ordering: base frames in publish order first, then
    deltas layer-major — every frame of an early layer precedes any
    frame of a later one, rounds ascending within a layer."""
    rng = np.random.default_rng(2)
    reg = ModelRegistry()
    params, _ = _publish_with_deltas(reg, rng, rounds=3, layers=3,
                                     dims=(8, 6, 4))
    order = list(params)
    plan = reg.pending("v1", since_round=0, need_base=True)

    bases = [f for f in plan if f["base"]]
    deltas = [f for f in plan if not f["base"]]
    # all base frames precede all delta frames, in publish order
    assert plan[:len(bases)] == bases
    assert [f["layer"] for f in bases] == order
    # deltas: layer-major in publish order, rounds ascending per layer
    ranks = [(order.index(f["layer"]), f["round"]) for f in deltas]
    assert ranks == sorted(ranks)
    # incremental pull skips the base and earlier rounds entirely
    inc = reg.pending("v1", since_round=2, need_base=False)
    assert all(not f["base"] and f["round"] > 2 for f in inc)
    assert len(inc) == len(order)


def test_torn_journal_tail_truncates_and_replays(tmp_path):
    """kill -9 mid-append: garbage after the last complete journal
    record is physically truncated on reload and the replayed registry
    still materializes bit-exact."""
    rng = np.random.default_rng(3)
    reg = ModelRegistry(durable_dir=str(tmp_path))
    params, dense = _publish_with_deltas(reg, rng, rounds=3)
    reg.close()

    journal = os.path.join(str(tmp_path), "registry.journal")
    size = os.path.getsize(journal)
    with open(journal, "ab") as f:
        f.write(b"\x00TORN-MID-DELTA\xff" * 3)

    reg2 = ModelRegistry(durable_dir=str(tmp_path))
    assert os.path.getsize(journal) == size  # tail physically gone
    mat = reg2.materialize("v1")
    for k in params:
        assert np.array_equal(mat[k], dense[k]), k
    # dedup state survived the restart too
    assert reg2.apply_delta("v1", next(iter(params)), 1,
                            np.ones(1, np.float32),
                            np.zeros(1, np.int64), sender=1,
                            rid="anything") is False
    reg2.close()


def test_generation_token_detects_restart(tmp_path):
    """Every construction on the same durable dir bumps the persisted
    generation; a replica sync across a server restart reports
    restart_detected without needing a full re-pull."""
    rng = np.random.default_rng(4)
    reg = ModelRegistry(durable_dir=str(tmp_path))
    params, dense = _publish_with_deltas(reg, rng, rounds=2)
    reg.close()

    srv = RegistryServer(durable_dir=str(tmp_path))
    srv.start()
    cli = RegistryClient(srv.addr, sender=5, timeout_s=10.0)
    rep = ServingReplica("v1")
    out = rep.sync(cli)
    assert out["applied"] > 0 and not out["restart_detected"]
    gen1 = out["gen"]
    cli.close()
    srv.crash()
    srv.join(5.0)

    srv2 = RegistryServer(durable_dir=str(tmp_path))
    srv2.start()
    assert srv2.generation == gen1 + 1
    cli2 = RegistryClient(srv2.addr, sender=5, timeout_s=10.0)
    out2 = rep.sync(cli2)
    assert out2["restart_detected"] is True
    assert rep.restarts_detected == 1
    for k in params:
        assert np.array_equal(rep.params()[k], dense[k]), k
    cli2.close()
    srv2.stop()
    srv2.join(5.0)


def test_compaction_preserves_state_and_dedup(tmp_path):
    """compact() folds the journal into the snapshot: the journal
    shrinks, the reopened registry is bit-exact and still rejects
    replays."""
    rng = np.random.default_rng(5)
    reg = ModelRegistry(durable_dir=str(tmp_path))
    params, dense = _publish_with_deltas(reg, rng, rounds=3)
    pre = reg.journal_bytes()
    reg.compact()
    assert reg.journal_bytes() < pre
    reg.close()

    reg2 = ModelRegistry(durable_dir=str(tmp_path))
    mat = reg2.materialize("v1")
    for k in params:
        assert np.array_equal(mat[k], dense[k]), k
    assert reg2.apply_delta("v1", next(iter(params)), 3,
                            np.ones(1, np.float32),
                            np.zeros(1, np.int64), sender=1,
                            rid="x") is False
    reg2.close()


def test_partial_round_push_is_not_lost_across_sync():
    """The train-while-serving race: push_delta is one PUSH per layer,
    so a replica sync can land when the registry holds round N for
    layer A but not yet layer B.  The per-layer since map must keep
    B's round-N delta pending — a global ``r > since`` cursor would
    filter it out forever and silently diverge the replica."""
    rng = np.random.default_rng(12)
    srv = RegistryServer()
    srv.start()
    trainer = RegistryClient(srv.addr, sender=0, timeout_s=10.0)
    params = {"0000/a": rng.normal(size=(8,)).astype(np.float32),
              "0001/b": rng.normal(size=(6,)).astype(np.float32)}
    trainer.publish("v1", params)
    dense = {k: v.copy() for k, v in params.items()}

    rcli = RegistryClient(srv.addr, sender=1, timeout_s=10.0)
    rep = ServingReplica("v1")
    rep.sync(rcli)
    try:
        # round 1: layer A lands, then the replica syncs IN the window
        # before layer B's round-1 push arrives
        va = np.float32([0.5, -0.5])
        ia = np.array([0, 3], np.int64)
        trainer.push_delta("v1", 1, {"0000/a": (va, ia)})
        np.add.at(dense["0000/a"], ia, va)
        mid = rep.sync(rcli)
        assert mid["applied"] == 1
        assert rep.last_round() == 1        # global cursor already at 1

        # layer B's round-1 delta lands late
        vb = np.float32([1.0])
        ib = np.array([2], np.int64)
        trainer.push_delta("v1", 1, {"0001/b": (vb, ib)})
        np.add.at(dense["0001/b"], ib, vb)

        # the next sync must still deliver B/1 (and dedup a re-sent A/1)
        out = rep.sync(rcli)
        assert out["applied"] == 1, "straggler layer's round was lost"
        served = rep.params()
        for k in dense:
            assert np.array_equal(served[k], dense[k]), k
    finally:
        trainer.close()
        rcli.close()
        srv.stop()
        srv.join(5.0)


def test_bad_push_answers_error_frame_not_dead_socket():
    """A PUSH for an unpublished version (or unknown layer) must come
    back as an ERROR frame the client surfaces as the real cause — not
    a torn-down connection retried into an opaque ConnectionError.
    The connection stays usable afterwards."""
    rng = np.random.default_rng(13)
    srv = RegistryServer()
    srv.start()
    cli = RegistryClient(srv.addr, sender=0, timeout_s=10.0)
    try:
        vals = np.ones(1, np.float32)
        idx = np.zeros(1, np.int64)
        with pytest.raises(RuntimeError, match="unpublished"):
            cli.push_delta("ghost", 1, {"0000/w": (vals, idx)})
        # unknown layer on a published version: also an ERROR frame
        cli.publish("v1", {"0000/w": rng.normal(size=(4,))
                           .astype(np.float32)})
        with pytest.raises(RuntimeError, match="no base layer"):
            cli.push_delta("v1", 1, {"9999/nope": (vals, idx)})
        # same socket still serves good pushes
        ack = cli.push_delta("v1", 1, {"0000/w": (vals, idx)})
        assert ack["applied_layers"] == 1
        assert cli.replays_sent == 0        # no blind reconnect-retry
    finally:
        cli.close()
        srv.stop()
        srv.join(5.0)


# --------------------------------------------------------------------------
# replica
# --------------------------------------------------------------------------

def test_replica_dedups_replayed_frames():
    """The replica's own (layer, round) dedup: applying the same delta
    twice leaves params bit-identical and counts the replay."""
    rng = np.random.default_rng(6)
    rep = ServingReplica("v1")
    base = rng.normal(size=(10,)).astype(np.float32)
    rep.install_base("0000/w", base, order=0)
    vals = rng.normal(size=3).astype(np.float32)
    idx = np.array([1, 4, 7], np.int64)
    assert rep.apply_delta("0000/w", 1, vals, idx)
    once = rep.params()["0000/w"].copy()
    assert rep.apply_delta("0000/w", 1, vals, idx) is False
    assert np.array_equal(rep.params()["0000/w"], once)
    assert rep.replays_deduped == 1
    expect = base.copy()
    np.add.at(expect, idx, vals)
    assert np.array_equal(once, expect)


def test_replica_staleness_tracking():
    rep = ServingReplica("v1")
    assert rep.staleness_s() == float("inf")
    assert rep.snapshot()["staleness_s"] is None
    rep.install_base("0000/w", np.zeros(4, np.float32), order=0)
    # freshness is monotonic-clock: a wall step cannot corrupt it
    assert rep.staleness_s(rep._refresh_mono + 2.5) == pytest.approx(2.5)
    assert rep.snapshot()["staleness_s"] is not None


# --------------------------------------------------------------------------
# gateway: continuous batching
# --------------------------------------------------------------------------

def _matmul_gateway(max_batch=8, queue_ms=2.0, dim=6, out_dim=3, seed=7):
    rng = np.random.default_rng(seed)
    rep = ServingReplica("v1")
    W = rng.normal(size=(dim, out_dim)).astype(np.float32)
    rep.install_base("0000/w", W, order=0)
    gw = InferenceGateway(rep, treedef=None, max_batch=max_batch,
                          queue_ms=queue_ms,
                          apply_fn=lambda named, xb: xb @ named["0000/w"])
    return gw, rep, W


def test_gateway_padding_buckets_and_jit_cache_bounded():
    """Padded power-of-two buckets bound the jit cache: many distinct
    batch sizes for one input shape compile at most len(buckets)
    executables, and every forward pads UP to a bucket."""
    gw, rep, W = _matmul_gateway(max_batch=8)
    assert gw.buckets == default_buckets(8) == (1, 2, 4, 8)
    assert [gw.bucket_for(n) for n in (1, 2, 3, 5, 8)] == [1, 2, 4, 8, 8]
    gw.start()
    try:
        for n in (1, 2, 3, 4, 5, 7, 8):
            reqs = [gw.submit(np.full(6, i + 1, np.float32))
                    for i in range(n)]
            for r in reqs:
                assert r.event.wait(30), "request timed out"
                assert r.error is None, r.error
                assert r.batch_size <= 8
                assert r.bucket in gw.buckets
                assert r.bucket >= r.batch_size
                np.testing.assert_allclose(
                    np.asarray(r.result),
                    np.full(6, 1, np.float32) * (r.x[0]) @ W,
                    rtol=1e-5)
        # one input shape -> at most one executable per bucket
        assert gw.jit_cache_size() <= len(gw.buckets)
    finally:
        gw.stop()


def test_gateway_coalesces_up_to_max_batch():
    """Requests submitted together coalesce into one forward (batch
    > 1) instead of one dispatch each."""
    gw, rep, W = _matmul_gateway(max_batch=4, queue_ms=25.0)
    gw.start()
    try:
        reqs = [gw.submit(np.ones(6, np.float32)) for _ in range(4)]
        for r in reqs:
            assert r.event.wait(30)
            assert r.error is None
        assert max(r.batch_size for r in reqs) > 1
        assert gw.batches_dispatched < len(reqs)
    finally:
        gw.stop()


def test_gateway_shed_is_explicit_not_lost():
    """A shed request still completes — error == "shed", the event
    fires, the ledger records it.  Nothing is silently dropped."""
    reset_request_ledger()
    gw, rep, W = _matmul_gateway()
    gw.start()
    try:
        gw.set_shed_fraction(1.0)
        r = gw.submit(np.ones(6, np.float32))
        assert r.event.wait(10)
        assert r.error == "shed"
        gw.set_shed_fraction(0.0)
        r2 = gw.submit(np.ones(6, np.float32))
        assert r2.event.wait(10) and r2.error is None
        assert gw.requests_shed == 1
    finally:
        gw.stop()


def test_unflatten_params_handles_five_digit_leaf_indices():
    """10000+ leaves: "10000..." sorts lexicographically before
    "9999...", so unflatten must order by the parsed integer leaf-index
    prefix, not by name string — a silent reorder is corrupt params."""
    import jax  # noqa: F401 — tree round-trip needs jax

    from geomx_tpu.serve.gateway import flatten_params, unflatten_params

    tree = [np.float32([i]) for i in range(10001)]
    named, treedef = flatten_params(tree)
    assert sorted(named) != list(named)     # lexicographic order lies
    rebuilt = unflatten_params(treedef, named)
    assert all(np.array_equal(a, b) for a, b in zip(rebuilt, tree))
    # a gap in the index sequence is refused, never silently reordered
    broken = dict(named)
    broken.pop(next(iter(broken)))
    with pytest.raises(ValueError, match="contiguous"):
        unflatten_params(treedef, broken)


def test_timed_out_request_never_counted_ok():
    """A request that ages out in the queue answers 500/"timeout" and
    is SKIPPED when the worker later reaches it — dispatching it anyway
    would count it "ok" in metrics/ledger after the client already got
    its 500, overcounting successes under overload."""
    reset_request_ledger()
    rng = np.random.default_rng(14)
    rep = ServingReplica("v1")
    rep.install_base("0000/w", rng.normal(size=(6, 3)).astype(np.float32),
                     order=0)
    gw = InferenceGateway(rep, treedef=None, max_batch=4, queue_ms=1.0,
                          apply_fn=lambda named, xb: xb @ named["0000/w"],
                          request_timeout_s=0.05)
    # worker NOT started: the request times out while still queued
    status, body, _ = gw.infer_route(
        json.dumps({"inputs": [[1, 0, 0, 0, 0, 0]]}).encode())
    assert status == 500 and b"timeout" in body
    assert gw.requests_timeout == 1
    # now the worker drains the stale entry: skipped, never forwarded
    gw.start()
    deadline = time.time() + 5.0
    while gw._queue.qsize() and time.time() < deadline:
        time.sleep(0.01)
    time.sleep(0.05)
    gw.stop()
    assert gw.requests_ok == 0
    assert gw.batches_dispatched == 0
    assert gw.surface_snapshot()["requests"]["timeout"] == 1


def test_gateway_stop_drains_queue():
    """stop() answers every queued request (error="shutdown") rather
    than stranding callers on their events."""
    gw, rep, W = _matmul_gateway(max_batch=2, queue_ms=50.0)
    gw.start()
    reqs = [gw.submit(np.ones(6, np.float32)) for _ in range(6)]
    gw.stop()
    for r in reqs:
        assert r.event.wait(10), "stranded request"
        assert r.error is None or r.error in ("shutdown", "shed")


# --------------------------------------------------------------------------
# request ledger
# --------------------------------------------------------------------------

def test_request_ledger_phases_and_percentiles():
    led = RequestLedger(capacity=64)
    t0 = 1000.0
    for i in range(100):
        led.observe(rid=i, t_enqueue=t0 + i * 0.01,
                    queue_s=0.001 * (i + 1), forward_s=0.002,
                    reply_s=0.0005, batch_size=4, bucket=4)
    s = led.summary()
    assert s["observed_total"] == 100
    assert s["requests"] == 64  # bounded ring
    for phase in REQUEST_PHASES + ("total",):
        assert s[f"{phase}_p50_s"] <= s[f"{phase}_p99_s"]
    # ring keeps the newest: queue_s there spans [0.037, 0.100], so
    # p99 sits at the top of that window (nearest-rank)
    assert 0.098 <= s["queue_p99_s"] <= 0.100
    assert s["batch_size_mean"] == pytest.approx(4.0)
    assert s["qps"] > 0
    assert s["by_status"] == {"ok": 64}


def test_request_ledger_tracks_status():
    led = RequestLedger(capacity=16)
    led.observe(rid=1, t_enqueue=0.0, queue_s=0.1, forward_s=0.0,
                reply_s=0.0, batch_size=0, bucket=0, status="shed")
    led.observe(rid=2, t_enqueue=0.1, queue_s=0.01, forward_s=0.01,
                reply_s=0.001, batch_size=1, bucket=1)
    s = led.summary()
    assert s["by_status"] == {"ok": 1, "shed": 1}
    # percentiles computed over ok records only
    assert s["queue_p99_s"] == pytest.approx(0.01)


def test_request_ledger_wire_lanes_per_direction_honesty():
    """account_wire keeps per-transport rx/tx byte lanes with the
    honesty ratio PER DIRECTION: a request lane at 1% framing overhead
    must not be masked (or indicted) by tiny header-dominated replies
    sharing the transport."""
    led = RequestLedger(capacity=16)
    led.account_wire("native", "rx", 1010, declared=1000)
    led.account_wire("native", "tx", 200, declared=100)
    led.account_wire("native", "rx", 50)            # undeclared frame
    led.account_wire("http", "rx", 300)
    s = led.summary()
    lane = s["wire"]["native"]
    assert lane["rx_bytes"] == 1060 and lane["tx_bytes"] == 200
    assert lane["frames"] == 3
    # undeclared frames count bytes but never enter the honesty ratio
    assert lane["rx_declared"] == 1000
    assert lane["rx_declared_actual"] == 1010
    assert lane["honesty_ratio_rx"] == pytest.approx(1.01)
    assert lane["honesty_ratio_tx"] == pytest.approx(2.0)
    http = s["wire"]["http"]
    assert http["honesty_ratio_rx"] is None         # nothing declared
    assert http["rx_bytes"] == 300


# --------------------------------------------------------------------------
# SLO policy
# --------------------------------------------------------------------------

def _obs(step, links=None):
    return ControlObservation(step=step, links=links or {},
                              exposed_comms=0.0, hidden_comms=0.0,
                              compute_s=0.0, ef_residual_norm=0.0,
                              grad_norm=0.0, dc_dense_bytes=0)


def test_slo_policy_shed_hysteresis_and_bounds():
    """Schmitt-trigger shedding: confirm streaks gate both directions,
    the hysteresis band holds, moves are bounded steps clamped to
    [0, shed_max]."""
    stats = {"p99_s": 0.1}
    pol = SloPolicy(lambda: stats, target_p99_s=0.5, shed_step=0.4,
                    shed_max=0.6, confirm=2, cooldown=1)
    assert pol.decide(_obs(0)) is None

    stats["p99_s"] = 3.0
    assert pol.decide(_obs(1)) is None          # confirm streak 1/2
    d = pol.decide(_obs(2))
    assert d.value == ("shed", 0.4) and d.kind == "slo"
    assert pol.decide(_obs(3)) is None          # streak reset on fire
    d = pol.decide(_obs(4))
    assert d.value == ("shed", 0.6)             # clamped at shed_max

    stats["p99_s"] = 0.3                        # inside the band: hold
    for s in range(5, 9):
        assert pol.decide(_obs(s)) is None

    stats["p99_s"] = 0.05                       # below release
    assert pol.decide(_obs(9)) is None
    d = pol.decide(_obs(10))
    assert d.value == ("shed", pytest.approx(0.2))
    # decisions replay deterministically through to_json
    assert json.loads(json.dumps(d.to_json()))["kind"] == "slo"


def test_slo_policy_routes_on_widest_confident_uplink():
    stats = {"p99_s": 0.1}
    pol = SloPolicy(lambda: stats, peer="global", min_confidence=0.5)
    links = {
        "0:g": {"party": 0, "peer": "global",
                "throughput_bps": 1e6, "confidence": 0.9},
        "1:g": {"party": 1, "peer": "global",
                "throughput_bps": 9e6, "confidence": 0.9},
    }
    d = pol.decide(_obs(1, links))
    assert d is not None and d.value[0] == "route"
    # degrade the chosen uplink hard: the route re-forms
    links["1:g"]["throughput_bps"] = 1e3
    d2 = None
    for s in range(2, 8):
        d2 = pol.decide(_obs(s, links))
        if d2 is not None:
            break
    assert d2 is not None and d2.value[0] == "route"
    assert d2.value != d.value


def test_pilot_accepts_slo_family():
    pilot = GraftPilot(sensors=None,
                       slo=SloPolicy(lambda: {"p99_s": 0.0}))
    assert len(pilot.policies) == 1
    assert pilot.policies[0].knob == "slo"


# --------------------------------------------------------------------------
# surfaces: healthz + metrics + /infer
# --------------------------------------------------------------------------

def test_serving_surface_registry_merges_providers():
    reset_serving_surface()
    assert serving_surface() is None
    register_serving_surface("a", lambda: {"x": 1})
    register_serving_surface("b", lambda: {"y": 2})
    assert serving_surface() == {"a": {"x": 1}, "b": {"y": 2}}
    register_serving_surface("a", None)
    assert serving_surface() == {"b": {"y": 2}}
    reset_serving_surface()


def test_gateway_http_healthz_metrics_and_infer():
    """The scheduler-shared HTTP surface: POST /infer coalesces and
    answers, /healthz exposes versions + freshness + queue depth, and
    the three geomx_serve_* metrics export."""
    reset_request_ledger()
    reset_serving_surface()
    gw, rep, W = _matmul_gateway(dim=4)
    gw.start()
    httpd = gw.serve_http(port=0)
    port = httpd.server_address[1]
    try:
        body = json.dumps({"inputs": [[1, 0, 0, 0], [0, 1, 0, 0]]}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/infer", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=10) as r:
            doc = json.loads(r.read())
        np.testing.assert_allclose(doc["outputs"][0], W[0], rtol=1e-6)
        np.testing.assert_allclose(doc["outputs"][1], W[1], rtol=1e-6)
        assert doc["version"] == "v1"

        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=10) as r:
            h = json.loads(r.read())
        srv = h["serving"]["gateway"]
        assert srv["replica"]["version"] == "v1"
        assert srv["replica"]["staleness_s"] is not None
        assert srv["queue_depth"] == 0
        assert srv["requests"]["ok"] >= 2

        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
            text = r.read().decode()
        for name in ("geomx_serve_requests_total",
                     "geomx_serve_batch_size",
                     "geomx_serve_replica_staleness_seconds"):
            assert name in text, name

        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/ledger", timeout=10) as r:
            led = json.loads(r.read())
        assert led["requests"]["summary"]["observed_total"] >= 2
    finally:
        httpd.shutdown()
        gw.stop()
        reset_serving_surface()


def test_infer_route_rejects_bad_payloads():
    gw, rep, W = _matmul_gateway()
    status, body, ctype = gw.infer_route(b"not json")
    assert status == 400
    status, body, ctype = gw.infer_route(json.dumps({"nope": 1}).encode())
    assert status == 400


# --------------------------------------------------------------------------
# config knobs + jaxpr pin
# --------------------------------------------------------------------------

def test_serve_knobs_from_env(monkeypatch):
    monkeypatch.setenv("GEOMX_SERVE_PORT", "9090")
    monkeypatch.setenv("GEOMX_SERVE_MAX_BATCH", "32")
    monkeypatch.setenv("GEOMX_SERVE_QUEUE_MS", "7.5")
    monkeypatch.setenv("GEOMX_SERVE_STALENESS_S", "30")
    monkeypatch.setenv("GEOMX_SERVE_TIMEOUT_S", "12.5")
    monkeypatch.setenv("GEOMX_SERVE_WARMUP", "0")
    monkeypatch.setenv("GEOMX_SERVE_NATIVE_WIRE", "0")
    monkeypatch.setenv("GEOMX_FLEETSCOPE", "1")
    monkeypatch.setenv("GEOMX_FLEETSCOPE_INTERVAL_S", "0.5")
    monkeypatch.setenv("GEOMX_FLEETSCOPE_BURN_WINDOWS", "30:2,120:1")
    cfg = GeoConfig.from_env()
    assert cfg.serve_port == 9090
    assert cfg.serve_max_batch == 32
    assert cfg.serve_queue_ms == 7.5
    assert cfg.serve_staleness_s == 30.0
    assert cfg.serve_timeout_s == 12.5
    assert cfg.serve_warmup is False
    assert cfg.serve_native_wire is False
    assert cfg.fleetscope is True
    assert cfg.fleetscope_interval_s == 0.5
    assert cfg.fleetscope_burn_windows == "30:2,120:1"
    # the gateway's default request deadline comes from the same knob
    rep = ServingReplica("v1")
    gw = InferenceGateway(rep, treedef=None,
                          apply_fn=lambda named, xb: xb)
    assert gw.request_timeout_s == 12.5


def test_serve_knobs_keep_jaxpr_byte_identical(monkeypatch):
    """The serving plane is host-plane only: configuring every
    GEOMX_SERVE_* knob must leave the traced train step byte-identical
    to a clean-environment build (the same overhead guarantee the
    telemetry and compute-engine knobs carry)."""
    import jax
    import optax

    from geomx_tpu.models import MLP
    from geomx_tpu.sync import get_sync_algorithm
    from geomx_tpu.telemetry.probes import canonicalize_jaxpr
    from geomx_tpu.topology import HiPSTopology
    from geomx_tpu.train import Trainer

    def build():
        topo = HiPSTopology(num_parties=2, workers_per_party=1)
        cfg = GeoConfig.from_env()
        cfg = GeoConfig(num_parties=2, workers_per_party=1,
                        compression="bsc,0.05,min_sparse_size=16",
                        telemetry=False,
                        serve_port=cfg.serve_port,
                        serve_max_batch=cfg.serve_max_batch,
                        serve_queue_ms=cfg.serve_queue_ms,
                        serve_staleness_s=cfg.serve_staleness_s,
                        serve_timeout_s=cfg.serve_timeout_s,
                        serve_warmup=cfg.serve_warmup,
                        serve_native_wire=cfg.serve_native_wire,
                        fleetscope=cfg.fleetscope,
                        fleetscope_interval_s=cfg.fleetscope_interval_s,
                        fleetscope_burn_windows=cfg.fleetscope_burn_windows)
        return Trainer(MLP(num_classes=10, hidden=(32,)), topo,
                       optax.sgd(0.1), sync=get_sync_algorithm(cfg),
                       config=cfg, donate=False)

    for var in ("GEOMX_SERVE_PORT", "GEOMX_SERVE_MAX_BATCH",
                "GEOMX_SERVE_QUEUE_MS", "GEOMX_SERVE_STALENESS_S",
                "GEOMX_SERVE_TIMEOUT_S", "GEOMX_SERVE_WARMUP",
                "GEOMX_SERVE_NATIVE_WIRE", "GEOMX_FLEETSCOPE",
                "GEOMX_FLEETSCOPE_INTERVAL_S",
                "GEOMX_FLEETSCOPE_BURN_WINDOWS"):
        monkeypatch.delenv(var, raising=False)
    tr = build()
    rng = np.random.RandomState(0)
    x = (rng.rand(2, 1, 4, 8, 8, 3) * 255).astype(np.uint8)
    y = rng.randint(0, 10, size=(2, 1, 4)).astype(np.int32)
    state = tr.init_state(jax.random.PRNGKey(0), x[0, 0, :2])
    sharding = tr.topology.batch_sharding(tr.mesh)
    xb, yb = jax.device_put(x, sharding), jax.device_put(y, sharding)
    j_clean = canonicalize_jaxpr(
        str(jax.make_jaxpr(tr.train_step)(state, xb, yb)))

    monkeypatch.setenv("GEOMX_SERVE_PORT", "18080")
    monkeypatch.setenv("GEOMX_SERVE_MAX_BATCH", "64")
    monkeypatch.setenv("GEOMX_SERVE_QUEUE_MS", "9.0")
    monkeypatch.setenv("GEOMX_SERVE_STALENESS_S", "1.0")
    monkeypatch.setenv("GEOMX_SERVE_TIMEOUT_S", "5.0")
    monkeypatch.setenv("GEOMX_SERVE_WARMUP", "0")
    monkeypatch.setenv("GEOMX_SERVE_NATIVE_WIRE", "0")
    monkeypatch.setenv("GEOMX_FLEETSCOPE", "1")
    monkeypatch.setenv("GEOMX_FLEETSCOPE_INTERVAL_S", "0.25")
    monkeypatch.setenv("GEOMX_FLEETSCOPE_BURN_WINDOWS", "30:2")
    tr2 = build()
    j_serving = canonicalize_jaxpr(
        str(jax.make_jaxpr(tr2.train_step)(state, xb, yb)))
    assert j_serving == j_clean


# --------------------------------------------------------------------------
# train-while-serving (wire, in-process)
# --------------------------------------------------------------------------

def test_train_while_serving_delta_refresh_bit_exact(tmp_path):
    """The tentpole loop in miniature: publish once, then rounds of
    sparse deltas streamed to a serving replica while the gateway
    answers — params track the trainer's dense checkpoint bit-exactly
    after every refresh."""
    rng = np.random.default_rng(8)
    srv = RegistryServer(durable_dir=str(tmp_path))
    srv.start()
    trainer = RegistryClient(srv.addr, sender=0, timeout_s=10.0)
    params = {"0000/w": rng.normal(size=(6, 3)).astype(np.float32),
              "0001/b": rng.normal(size=(3,)).astype(np.float32)}
    trainer.publish("v1", params)
    dense = {k: v.copy() for k, v in params.items()}

    replica_cli = RegistryClient(srv.addr, sender=1, timeout_s=10.0)
    rep = ServingReplica("v1", party=1)
    rep.sync(replica_cli)

    gw = InferenceGateway(
        rep, treedef=None, max_batch=4, queue_ms=2.0,
        apply_fn=lambda named, xb:
            xb @ named["0000/w"] + named["0001/b"])
    gw.start()
    try:
        for r in range(1, 4):
            layers = {}
            for k, v in dense.items():
                idx = rng.choice(v.size, size=2,
                                 replace=False).astype(np.int64)
                vals = rng.normal(size=2).astype(np.float32)
                layers[k] = (vals, idx)
                np.add.at(v.reshape(-1), idx, vals)
            ack = trainer.push_delta("v1", r, layers)
            assert ack["applied_layers"] == len(layers)
            out = rep.sync(replica_cli)
            assert out["applied"] == len(layers)
            served = rep.params()
            for k in dense:
                assert np.array_equal(served[k], dense[k]), (r, k)
            # gateway answers from the refreshed weights immediately
            x = np.ones(6, np.float32)
            req = gw.submit(x)
            assert req.event.wait(30) and req.error is None
            np.testing.assert_allclose(
                np.asarray(req.result),
                x @ dense["0000/w"] + dense["0001/b"], rtol=1e-5)
    finally:
        gw.stop()
        trainer.close()
        replica_cli.close()
        srv.stop()
        srv.join(5.0)


def test_delta_refresh_bytes_on_the_round_ledger(tmp_path):
    """Delta-only refresh by the wire's own books: the registry frames
    carry their round and declared size, so the round ledger attributes
    every pushed byte.  The base publish is round 0; each later round's
    pair frames declare no more than arrived and are a fraction of the
    dense checkpoint."""
    reset_round_ledger()
    rng = np.random.default_rng(11)
    srv = RegistryServer(durable_dir=str(tmp_path))
    srv.start()
    trainer = RegistryClient(srv.addr, sender=0, timeout_s=10.0)
    params = {"0000/w": rng.normal(size=(256, 64)).astype(np.float32),
              "0001/b": rng.normal(size=(4096,)).astype(np.float32)}
    dense_bytes = sum(v.nbytes for v in params.values())
    rounds = 3
    try:
        trainer.publish("v1", params)
        for r in range(1, rounds + 1):
            layers = {}
            for k, v in params.items():
                n = v.size // 100
                layers[k] = (rng.normal(size=n).astype(np.float32),
                             rng.choice(v.size, size=n,
                                        replace=False).astype(np.int64))
            trainer.push_delta("v1", r, layers)
        base_rx = delta_rx = 0
        for rec in get_round_ledger().records():
            if not str(rec.get("key", "")).startswith("v1/"):
                continue
            got = int(rec["wire"].get("push_rx_bytes", 0))
            if int(rec["round"]) == 0:
                base_rx += got
            else:
                delta_rx += got
                assert 0 < int(rec["declared_rx_bytes"]) <= got, rec
        assert base_rx >= dense_bytes
        assert 0 < delta_rx / rounds < 0.5 * dense_bytes
    finally:
        trainer.close()
        srv.stop()
        srv.join(5.0)
        reset_round_ledger()


# --------------------------------------------------------------------------
# serving fast path (docs/serving.md "Serving fast path")
# --------------------------------------------------------------------------

def test_gateway_prewarm_compiles_before_first_request():
    """start() compiles every (bucket, input shape) executable up
    front; serving any batch size afterwards adds ZERO compiles — the
    jit cache holds exactly what warmup built (the r01 p99/p50 gap was
    first-request compiles landing inside request latency)."""
    gw, rep, W = _matmul_gateway(max_batch=8)
    gw.warmup_shapes = [(6,)]
    gw._warmup_enabled = True
    gw.start()
    try:
        assert gw.warmup_compiles == len(gw.buckets) == 4
        assert gw.jit_cache_size() == gw.warmup_compiles
        for n in (1, 3, 5, 8):
            reqs = [gw.submit(np.full(6, i + 1, np.float32))
                    for i in range(n)]
            for r in reqs:
                assert r.event.wait(30) and r.error is None
        # the pin: no request paid a compile after warmup
        assert gw.jit_cache_size() == gw.warmup_compiles
        assert gw.surface_snapshot()["warmup_compiles"] == 4
    finally:
        gw.stop()


def test_gateway_concurrent_load_zero_lost_exact_shed():
    """Concurrent submitters driven through queue_cap pressure: every
    request resolves to exactly one of ok/shed/timeout (zero silent
    loss) and the shed counter matches the shed outcomes exactly —
    the books the zero-lost acceptance gate audits."""
    rng = np.random.default_rng(3)
    rep = ServingReplica("v1")
    W = rng.normal(size=(6, 3)).astype(np.float32)
    rep.install_base("0000/w", W, order=0)
    gw = InferenceGateway(
        rep, treedef=None, max_batch=4, queue_ms=1.0, queue_cap=8,
        apply_fn=lambda named, xb: xb @ named["0000/w"])
    gw.start()
    results = []
    lock = threading.Lock()

    def loadgen(wid):
        r = np.random.default_rng(100 + wid)
        got = []
        for _ in range(40):
            req = gw.submit(r.normal(size=6).astype(np.float32))
            assert req.event.wait(30), "request never resolved"
            got.append(req.error or "ok")
        with lock:
            results.extend(got)

    threads = [threading.Thread(target=loadgen, args=(w,))
               for w in range(8)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
    finally:
        gw.stop()
    assert len(results) == 8 * 40          # zero lost: all resolved
    counts = {k: results.count(k) for k in set(results)}
    assert set(counts) <= {"ok", "shed", "timeout"}, counts
    assert counts.get("ok", 0) == gw.requests_ok
    assert counts.get("shed", 0) == gw.requests_shed
    assert counts.get("timeout", 0) == gw.requests_timeout
    assert gw.requests_error == 0
    assert (gw.requests_ok + gw.requests_shed + gw.requests_timeout
            == 8 * 40)


def test_replica_o1_fast_path_bit_exact_and_counted():
    """The ping-pong O(k) refresh: after the first two rounds of a
    layer, applies scatter into the retired spare buffer instead of
    dense-copying — counted in o1_applies — and the served weights
    stay bit-exact vs an np.add.at dense checkpoint throughout, even
    while a reader holds an old snapshot (that costs exactly one dense
    fallback, never a torn read)."""
    rng = np.random.default_rng(11)
    rep = ServingReplica("v1")
    base = rng.normal(size=(64,)).astype(np.float32)
    rep.install_base("0000/w", base, order=0)
    dense = base.copy()
    held = rep.params()                     # a reader keeps round-0
    held_copy = {k: v.copy() for k, v in held.items()}
    for r in range(1, 21):
        idx = rng.choice(64, size=7, replace=False).astype(np.int64)
        vals = rng.normal(size=7).astype(np.float32)
        assert rep.apply_delta("0000/w", r, vals, idx)
        np.add.at(dense, idx, vals)
        assert np.array_equal(rep.params()["0000/w"], dense), r
    # the held snapshot was never scattered into
    assert np.array_equal(held["0000/w"], held_copy["0000/w"])
    snap = rep.snapshot()
    assert snap["o1_applies"] > 0
    # rounds not covered by the fast path fell back to dense copies —
    # both paths together account for every apply
    assert snap["o1_applies"] + snap["dense_copies"] == 20


def test_native_wire_roundtrip_and_ledger_accounting():
    """The native INFER/INFER_REPLY lane end to end: one persistent
    connection, correct outputs on the same queue as local submits,
    byte-true rx/tx lanes in the request ledger with the declared-
    payload honesty ratio bounded on the request direction."""
    from geomx_tpu.serve.infer_wire import (NativeInferenceClient,
                                            NativeInferenceServer)
    from geomx_tpu.telemetry.ledger import get_request_ledger
    reset_request_ledger()
    threads_before = set(threading.enumerate())
    # serving-sized features (the honesty bound is about framing
    # overhead amortized over REAL payloads, not a 48-byte toy row)
    gw, rep, W = _matmul_gateway(max_batch=8, dim=784)
    gw.start()
    srv = NativeInferenceServer(gw, port=0).start()
    cli = NativeInferenceClient(("127.0.0.1", srv.port), timeout_s=20.0)
    try:
        x = np.arange(2 * 784, dtype=np.float32).reshape(2, 784) / 784.0
        out = cli.infer(x)
        assert "error" not in out, out
        np.testing.assert_allclose(out["outputs"], x @ W, rtol=1e-4)
        assert out["version"] == "v1"
        assert len(out["batch_sizes"]) == 2
        # second frame on the SAME connection (persistent lane)
        out2 = cli.infer(np.ones((1, 784), np.float32))
        np.testing.assert_allclose(
            out2["outputs"], np.ones((1, 784), np.float32) @ W,
            rtol=1e-4)
        # the server counts a reply's bytes after it has sent them, and
        # the gateway's worker records a request after it has woken its
        # waiter, so the client may hold the reply before the ledger holds
        # either.  Each thread ends behind its last count: the
        # connection's at the client's close, the worker at `stop`
        cli.close()
        for thread in set(threading.enumerate()) - threads_before:
            if thread.name.endswith("(_serve_conn)"):
                thread.join(10.0)
        gw.stop()
        s = get_request_ledger().summary()
        assert s["by_transport"].get("native", 0) == 3
        lane = s["wire"]["native"]
        assert lane["frames"] == 4          # 2 rx + 2 tx
        # actual on-wire >= declared payload, within framing overhead
        assert lane["rx_declared_actual"] >= lane["rx_declared"] > 0
        assert lane["honesty_ratio_rx"] is not None
        assert 1.0 <= lane["honesty_ratio_rx"] <= 1.02
    finally:
        cli.close()
        srv.stop()
        gw.stop()


def test_native_wire_shed_is_explicit_reply_not_torn_socket():
    """A shed on the native lane answers an INFER_REPLY error frame on
    the same healthy connection — the client sees the refusal and the
    connection keeps working for the next request."""
    from geomx_tpu.serve.infer_wire import (NativeInferenceClient,
                                            NativeInferenceServer)
    gw, rep, W = _matmul_gateway(max_batch=4)
    gw.start()
    srv = NativeInferenceServer(gw, port=0).start()
    cli = NativeInferenceClient(("127.0.0.1", srv.port), timeout_s=20.0)
    try:
        gw.set_shed_fraction(1.0)
        out = cli.infer(np.ones((2, 6), np.float32))
        assert out.get("error") == "shed"
        assert out.get("shed") == 2
        gw.set_shed_fraction(0.0)
        ok = cli.infer(np.ones((1, 6), np.float32))
        assert "outputs" in ok              # same socket still serves
    finally:
        cli.close()
        srv.stop()
        gw.stop()
