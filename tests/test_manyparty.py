"""Many-party sharded global tier (docs/resilience.md "Many-party
global tier"): the scheduler-owned versioned key-range map, wrong-shard
redirects, scheduler-driven rebalance with exact-once merges, shard
failover onto a new port, deterministic sender-ordered merges, P3-safe
session resume, and the shard-targeted chaos grammar.

These tests pin the mechanisms at 2-4 parties in seconds.
"""

import threading
import time

import numpy as np
import pytest

from geomx_tpu.resilience.chaos import ChaosSchedule, shard_node_index
from geomx_tpu.service import (GeoPSClient, GeoPSServer, GeoScheduler,
                               SchedulerClient, ShardedGlobalClient,
                               WrongShardError,
                               start_sharded_global_tier)
from geomx_tpu.service.shardmap import (KEYSPACE, ShardMap, even_bounds,
                                        key_hash, moved_segments,
                                        rebalance_bounds)

# ---- shard map ------------------------------------------------------------


def test_even_bounds_cover_keyspace():
    for s in (1, 2, 4, 7):
        b = even_bounds(s)
        assert b[0] == 0 and b[-1] == KEYSPACE and len(b) == s + 1
        assert all(b[i] < b[i + 1] for i in range(s))


def test_shard_map_routing_and_meta_roundtrip():
    m = ShardMap.initial([("127.0.0.1", 9000 + i) for i in range(4)])
    assert m.version == 1
    for k in (f"w{i}" for i in range(32)):
        i = m.shard_for(k)
        lo, hi = m.range_of(i)
        assert lo <= key_hash(k) < hi
    rt = ShardMap.from_meta(m.to_meta())
    assert rt == m


def test_shard_map_mutations_bump_version():
    m = ShardMap.initial([("127.0.0.1", 1), ("127.0.0.1", 2)])
    m2 = m.with_address(1, "127.0.0.1", 99)
    assert m2.version == 2 and m2.addr_of(1) == ("127.0.0.1", 99)
    assert m2.bounds == m.bounds
    m3 = m2.with_bounds((0, 123456, KEYSPACE))
    assert m3.version == 3 and m3.shards == m2.shards


def test_rebalance_bounds_follow_observed_load():
    m = ShardMap.initial([("h", 1), ("h", 2)])
    keys = [f"k{i}" for i in range(64)]
    hot = [k for k in keys if m.shard_for(k) == 0]
    # skew: everything lands on shard 0 -> the boundary must move left
    loads = {k: 100.0 for k in hot}
    nb = rebalance_bounds(m, loads, min_gain=0.05)
    assert nb != m.bounds
    m2 = m.with_bounds(nb)
    moved = [k for k in hot if m2.shard_for(k) != 0]
    assert moved, "a fully-skewed load must move some keys"
    segs = moved_segments(m, m2)
    assert segs and all(o != n for _lo, _hi, o, n in segs)
    # a required gain no real move can reach: the bounds stay put
    # (boundary churn has a migration cost)
    same = rebalance_bounds(m.with_bounds(nb), {k: 1.0 for k in moved},
                            min_gain=0.99)
    assert same == nb


# ---- chaos grammar: shard targeting ---------------------------------------


def test_chaos_shard_kill_roundtrip():
    spec = ("seed=9;kill@3:node=shard1,restart_after=2;"
            "kill@6:node=shard3,restart_after=1")
    s = ChaosSchedule.from_spec(spec)
    kinds = [(e.step, e.kind, e.node) for e in s.events]
    assert (3, "kill", "shard1") in kinds
    assert (5, "restart", "shard1") in kinds
    assert (7, "restart", "shard3") in kinds
    assert ChaosSchedule.from_spec(s.spec()).spec() == s.spec()
    assert shard_node_index("shard12") == 12
    assert shard_node_index("scheduler") is None


def test_chaos_bad_node_rejected():
    with pytest.raises(ValueError, match="shard<i>"):
        ChaosSchedule.from_spec("kill@1:node=gpu0")


def test_chaos_random_multi_node_deterministic_roundtrip():
    kwargs = dict(seed=4, steps=12, num_parties=16, blackouts=0,
                  node_kills=3,
                  nodes=("shard0", "shard1", "scheduler"),
                  corrupt_epochs=1, throttle_epochs=1)
    a = ChaosSchedule.random(**kwargs)
    b = ChaosSchedule.random(**kwargs)
    assert a.spec() == b.spec()
    assert ChaosSchedule.from_spec(a.spec()).spec() == a.spec()
    kills = [e for e in a.events if e.kind == "kill"]
    restarts = [e for e in a.events if e.kind == "restart"]
    # node_kills is an upper bound (pairs that no longer fit the run
    # are dropped); every emitted kill has its restart INSIDE the run
    assert 1 <= len(kills) <= 3 and len(restarts) == len(kills)
    assert all(e.step < 12 for e in restarts)
    # at most one outstanding kill per node: kills/restarts alternate
    for node in {e.node for e in kills}:
        seq = sorted((e.step, e.kind) for e in a.events
                     if e.kind in ("kill", "restart") and e.node == node)
        for (_s1, k1), (_s2, k2) in zip(seq, seq[1:]):
            assert k1 != k2, seq
    with pytest.raises(ValueError, match="shard<i>"):
        ChaosSchedule.random(seed=1, steps=4, num_parties=2,
                             blackouts=0, node_kills=1, nodes=("gpu",))


# ---- live tier fixtures ---------------------------------------------------


def _tier(tmp_path, shards=2, workers=2, durable=True):
    sched = GeoScheduler(durable_dir=str(tmp_path / "sched")
                         if durable else None).start()
    servers = start_sharded_global_tier(
        ("127.0.0.1", sched.port), num_shards=shards,
        num_workers=workers,
        durable_dir=str(tmp_path / "tier") if durable else None)
    return sched, servers


def _teardown(sched, servers, clients=()):
    for c in clients:
        try:
            c.close()
        except Exception:
            pass
    for s in servers:
        try:
            s.stop(forward=False)
        except Exception:
            pass
    sched.stop()


# ---- wrong-shard redirect -------------------------------------------------


def test_stale_map_gets_redirect_not_wrong_merge(tmp_path):
    sched, servers = _tier(tmp_path, shards=2, workers=1, durable=False)
    sc = SchedulerClient(("127.0.0.1", sched.port))
    try:
        m = ShardMap.from_meta(sc.shard_map())
        key = next(f"k{i}" for i in range(64) if m.shard_for(f"k{i}") == 0)
        right = GeoPSClient(m.addr_of(0), sender_id=0)
        right.init(key, np.zeros(8, np.float32))
        # a client with a stale (wrong) map dials shard 1 for shard 0's
        # key: every request type redirects, nothing merges
        wrong = GeoPSClient(m.addr_of(1), sender_id=0)
        for op in (lambda: wrong.init(key, np.zeros(8, np.float32)),
                   lambda: wrong.push(key, np.ones(8, np.float32)),
                   lambda: wrong.pull(key)):
            with pytest.raises(WrongShardError) as ei:
                op()
            assert ei.value.map_version == 1
        # the right shard's store is untouched by the redirected push
        right.push(key, np.ones(8, np.float32))
        assert np.allclose(right.pull(key), 1.0)
        wrong.close()
        right.close()
    finally:
        _teardown(sched, servers)


# ---- sharded routing end to end -------------------------------------------


def test_sharded_client_routes_and_merges_exactly(tmp_path):
    sched, servers = _tier(tmp_path, shards=2, workers=2)
    ws = [ShardedGlobalClient(("127.0.0.1", sched.port), sender_id=p,
                              reconnect=True) for p in range(2)]
    try:
        keys = [f"w{i}" for i in range(6)]
        for w in ws:
            for k in keys:
                w.init(k, np.zeros(16, np.float32))
        for _r in range(2):
            for k in keys:
                for p, w in enumerate(ws):
                    w.push(k, np.full(16, p + 1.0, np.float32))
                for w in ws:
                    w.pull(k)
        for k in keys:
            assert np.allclose(ws[0].pull(k), 6.0)   # 2 rounds x (1+2)
        prog = ws[0].progress()
        assert all(prog[k] == 2 for k in keys), prog
        # both shards actually own keys (the tier is really sharded)
        m = ShardMap.from_meta(ws[0]._sched.shard_map())
        owners = {m.shard_for(k) for k in keys}
        assert owners == {0, 1}
    finally:
        _teardown(sched, servers, ws)


def test_rebalance_mid_round_is_idempotent(tmp_path):
    """A rebalance moves a key while its round is OPEN: the migrated
    state carries the open round's contributions + per-sender counts,
    a replayed push at the new owner is an idempotent ACK, and the
    round completes with the exact sum."""
    sched, servers = _tier(tmp_path, shards=2, workers=2)
    ws = [ShardedGlobalClient(("127.0.0.1", sched.port), sender_id=p,
                              reconnect=True) for p in range(2)]
    sc = SchedulerClient(("127.0.0.1", sched.port))
    try:
        m = ShardMap.from_meta(sc.shard_map())
        hot = [f"h{i}" for i in range(64)
               if m.shard_for(f"h{i}") == 0][:4]
        cold = [f"c{i}" for i in range(64)
                if m.shard_for(f"c{i}") == 1][:1]
        for k in hot + cold:
            for w in ws:
                w.init(k, np.zeros(8, np.float32))
        for _r in range(2):     # skewed load onto shard 0
            for k in hot:
                for w in ws:
                    w.push(k, np.ones(8, np.float32))
                for w in ws:
                    w.pull(k)
        # open round 3 on every hot key: only worker 0 pushed
        for k in hot:
            ws[0].push(k, np.full(8, 3.0, np.float32))
        res = sc.rebalance_shards(min_gain=0.05)
        assert res["changed"] and res["moved_keys"] > 0
        m2 = ShardMap.from_meta(res["map"])
        moved = [k for k in hot if m2.shard_for(k) != 0]
        assert moved
        k0 = moved[0]
        # a resend crossing the rebalance: replay worker 0's round-3
        # push at the NEW owner — must dedup, not double-merge
        replay = GeoPSClient(m2.addr_of(m2.shard_for(k0)), sender_id=0)
        replay.push(k0, np.full(8, 3.0, np.float32),
                    meta={"round": 3})
        for k in hot:           # worker 1 completes round 3 everywhere
            ws[1].push(k, np.full(8, 3.0, np.float32))
        for k in hot:
            got = ws[0].pull(k, timeout=60.0)
            assert np.allclose(got, 10.0), (k, got[:3])  # 2*2 + 3 + 3
        prog = ws[0].progress()
        assert all(prog[k] == 3 for k in hot), prog
        replay.close()
    finally:
        sc.close()
        _teardown(sched, servers, ws)


def test_shard_failover_to_new_port_bumps_map_and_resumes(tmp_path):
    """Kill one shard; its journal replays into a replacement on a NEW
    port; `shard_failover` bumps the map; clients redirect and the
    training stream continues exactly — while the OTHER shard's keys
    never stall."""
    sched, servers = _tier(tmp_path, shards=2, workers=1)
    w = ShardedGlobalClient(("127.0.0.1", sched.port), sender_id=0,
                            reconnect=True, reconnect_timeout_s=2.0)
    sc = SchedulerClient(("127.0.0.1", sched.port))
    try:
        m = ShardMap.from_meta(sc.shard_map())
        k0 = next(f"k{i}" for i in range(64)
                  if m.shard_for(f"k{i}") == 0)
        k1 = next(f"k{i}" for i in range(64)
                  if m.shard_for(f"k{i}") == 1)
        for k in (k0, k1):
            w.init(k, np.zeros(8, np.float32))
            w.push(k, np.ones(8, np.float32))
            assert np.allclose(w.pull(k), 1.0)
        servers[0].crash()      # shard 0 dies; misses its window
        repl = GeoPSServer(num_workers=1, mode="sync", accumulate=True,
                           rank=0, shard_index=0,
                           shard_range=(m.bounds[0], m.bounds[1]),
                           shard_map_version=1,
                           durable_dir=str(tmp_path / "tier"),
                           durable_name="shard0").start()
        newmap = sc.shard_failover(0, "127.0.0.1", repl.port)
        assert newmap["version"] == 2
        servers[0] = repl
        # the surviving shard never stalled
        w.push(k1, np.ones(8, np.float32))
        assert np.allclose(w.pull(k1), 2.0)
        # the failed-over shard resumed its durable state
        w.push(k0, np.ones(8, np.float32))
        assert np.allclose(w.pull(k0, timeout=60.0), 2.0)
        assert w.map_version == 2
    finally:
        sc.close()
        _teardown(sched, servers, [w])


def test_scheduler_restart_restores_shard_map(tmp_path):
    sched, servers = _tier(tmp_path, shards=2, workers=1)
    port = sched.port
    sc = SchedulerClient(("127.0.0.1", port))
    try:
        m = sc.shard_map()
        assert m and m["version"] == 1
        sc.shard_failover(1, "127.0.0.1", 59999)   # bump to v2
        sc.close()
        sched.crash()
        sched2 = GeoScheduler(port=port,
                              durable_dir=str(tmp_path / "sched")).start()
        sc2 = SchedulerClient(("127.0.0.1", port))
        m2 = sc2.shard_map()
        assert m2["version"] == 2
        assert ["127.0.0.1", 59999] in m2["shards"]
        sc2.close()
        sched = sched2
    finally:
        _teardown(sched, servers)


# ---- deterministic merges -------------------------------------------------


def test_merge_is_sorted_sender_order_not_arrival_order():
    """Float addition is not associative: the round merge must be
    bit-identical regardless of push arrival order (the 16+-party
    bit-exact chaos gate stands on this)."""
    vals = {0: np.float32(1e8), 1: np.float32(-1e8), 2: np.float32(1.0)}
    outs = []
    for order in ((0, 1, 2), (2, 1, 0), (1, 2, 0)):
        srv = GeoPSServer(num_workers=3, mode="sync",
                          accumulate=True).start()
        cs = [GeoPSClient(("127.0.0.1", srv.port), sender_id=s)
              for s in range(3)]
        cs[0].init("w", np.zeros(4, np.float32))
        for s in order:
            cs[s].push("w", np.full(4, vals[s], np.float32))
        outs.append(np.asarray(cs[0].pull("w")))
        cs[0].stop_server()
        for c in cs:
            c.close()
        srv.join(5)
    assert np.array_equal(outs[0], outs[1])
    assert np.array_equal(outs[0], outs[2])


def _pairs_payload(vals, idx):
    from geomx_tpu.compression.sparseagg import encode_pairs_payload
    return encode_pairs_payload(np.asarray(vals, np.float32),
                                np.asarray(idx))


def test_sparse_merge_bit_exact_across_orders_and_rebalance(tmp_path):
    """The sorted-sender bit-equality contract extended to compressed
    (value, index) rounds (docs/performance.md "Compressed-domain
    aggregation"): a sparse round merges bit-identically across
    shuffled push arrival orders, AND across a mid-round shard
    rebalance — the open round's sparse contributions migrate in pair
    form (`_enc_contrib`) and complete at the new owner with the same
    bits."""
    n = 64
    meta = {"comp": "bsc", "n": n, "shape": [n]}
    # catastrophic-cancellation values: any reassociation changes bits
    payloads = {
        0: _pairs_payload([np.float32(1e8), 1.0], [3, 10]),
        1: _pairs_payload([np.float32(-1e8), 2.0], [3, 20]),
    }

    def run(shuffle, rebalance):
        sched, servers = _tier(tmp_path / f"t{shuffle}{rebalance}",
                               shards=2, workers=2)
        ws = [ShardedGlobalClient(("127.0.0.1", sched.port), sender_id=p,
                                  reconnect=True) for p in range(2)]
        sc = SchedulerClient(("127.0.0.1", sched.port))
        try:
            m = ShardMap.from_meta(sc.shard_map())
            hot = [f"h{i}" for i in range(64)
                   if m.shard_for(f"h{i}") == 0][:3]
            cold = [f"c{i}" for i in range(64)
                    if m.shard_for(f"c{i}") == 1][:1]
            for k in hot + cold:
                for w in ws:
                    w.init(k, np.zeros(n, np.float32))
            # a completed warm-up round builds the rebalance's load
            # window (sparse pushes count like dense ones)
            for k in hot:
                for p in (ws if not shuffle else ws[::-1]):
                    p.push(k, _pairs_payload([1.0], [5]),
                           meta=dict(meta))
                for w in ws:
                    w.pull(k)
            # open round 2: only worker 0 pushed its pairs
            for k in hot:
                ws[0].push(k, payloads[0], meta=dict(meta))
            if rebalance:
                res = sc.rebalance_shards(min_gain=0.05)
                assert res["changed"] and res["moved_keys"] > 0
                m2 = ShardMap.from_meta(res["map"])
                assert any(m2.shard_for(k) != 0 for k in hot)
            # worker 1 completes round 2 (re-routing via redirect when
            # the key moved)
            for k in hot:
                ws[1].push(k, payloads[1], meta=dict(meta))
            outs = {k: np.asarray(ws[0].pull(k, timeout=60.0))
                    for k in hot}
            prog = ws[0].progress()
            assert all(prog[k] == 2 for k in hot), prog
            return outs
        finally:
            sc.close()
            _teardown(sched, servers, ws)

    base = run(shuffle=False, rebalance=False)
    shuffled = run(shuffle=True, rebalance=False)
    rebal = run(shuffle=False, rebalance=True)
    for k, v in base.items():
        # accumulate store: round 1 (1.0 at idx 5) + the sparse round-2
        # merge in sorted-sender order
        exp = np.zeros(n, np.float32)
        exp[5] = 2.0
        exp[3] = np.float32(np.float32(1e8) + np.float32(-1e8))
        exp[10], exp[20] = 1.0, 2.0
        np.testing.assert_array_equal(v, exp, err_msg=k)
        np.testing.assert_array_equal(v, shuffled[k], err_msg=k)
        np.testing.assert_array_equal(v, rebal[k], err_msg=k)


# ---- P3-safe session resume + resend buffer -------------------------------


def test_reconnect_composes_with_p3_chunking(tmp_path):
    """The PR 10 loud rejection is gone: a chunked round's full chunk
    set is retained and replays through a mid-round restart."""
    srv = GeoPSServer(num_workers=2, mode="sync", accumulate=True,
                      durable_dir=str(tmp_path), durable_name="g").start()
    port = srv.port
    ca = GeoPSClient(("127.0.0.1", port), sender_id=0, reconnect=True,
                     p3_slice_elems=16)
    cb = GeoPSClient(("127.0.0.1", port), sender_id=1, reconnect=True,
                     p3_slice_elems=16)
    n = 100   # > 16 elems -> chunked
    try:
        for c in (ca, cb):
            c.init("w", np.zeros(n, np.float32))
        ca.push("w", np.full(n, 1.0, np.float32))
        cb.push("w", np.full(n, 2.0, np.float32))
        assert np.allclose(ca.pull("w"), 3.0)       # round 1 durable
        ca.push("w", np.full(n, 5.0, np.float32))   # round 2 in flight
        assert len(ca._last_push["w"][1]) > 1       # the CHUNK SET
        time.sleep(0.3)
        srv.crash()                                  # round 2 lost
        srv2 = GeoPSServer(num_workers=2, mode="sync", accumulate=True,
                           port=port, durable_dir=str(tmp_path),
                           durable_name="g").start()
        cb.push("w", np.full(n, 2.0, np.float32))
        assert np.allclose(cb.pull("w", timeout=60.0), 10.0)  # 3+5+2
        assert np.allclose(ca.pull("w", timeout=60.0), 10.0)
        ca.stop_server()
        srv2.join(5)
    finally:
        for c in (ca, cb):
            c.close()


def test_resend_buffer_released_on_pull_and_gauged():
    """Satellite fix: the retained re-push frame is released when the
    round's pull reply is consumed, and the retained bytes ride
    ``geomx_resend_buffer_bytes``."""
    from geomx_tpu.telemetry import get_registry
    srv = GeoPSServer(num_workers=1, mode="sync", accumulate=True).start()
    c = GeoPSClient(("127.0.0.1", srv.port), sender_id=77,
                    reconnect=True)
    try:
        c.init("w", np.zeros(64, np.float32))
        fam = get_registry().get("geomx_resend_buffer_bytes")

        def gauge():
            return dict(fam.children()).get(("77",)).value

        before = gauge()
        c.push("w", np.ones(64, np.float32))
        assert gauge() > before          # retained while in flight
        assert "w" in c._last_push
        c.pull("w")
        deadline = time.monotonic() + 5.0
        while "w" in c._last_push and time.monotonic() < deadline:
            time.sleep(0.01)
        assert "w" not in c._last_push   # released on the pull reply
        assert gauge() == before
        c.stop_server()
        srv.join(5)
    finally:
        c.close()


# ---- scheduler heartbeat sweep --------------------------------------------


def test_heartbeat_sweep_does_not_hold_lock_during_scan():
    """The dead/alive sweeps snapshot the beat table and evaluate
    outside the lock: a big roster scan can never block concurrent
    heartbeat() calls (and concurrent mutation can never corrupt the
    sweep).  Functional + hammer coverage."""
    from geomx_tpu.utils.heartbeat import HeartbeatMonitor
    mon = HeartbeatMonitor(timeout_s=0.2)
    for n in range(64):
        mon.heartbeat(n)
    assert mon.dead_nodes() == []
    stop = threading.Event()
    errs = []

    def hammer(base):
        try:
            while not stop.is_set():
                for n in range(base, base + 32):
                    mon.heartbeat(n)
        except Exception as e:   # pragma: no cover
            errs.append(repr(e))

    threads = [threading.Thread(target=hammer, args=(b,), daemon=True)
               for b in (1000, 2000)]
    for t in threads:
        t.start()
    for _ in range(200):
        mon.dead_nodes()
        mon.alive_nodes()
    stop.set()
    for t in threads:
        t.join(2.0)
    assert not errs
    time.sleep(0.3)
    dead = mon.dead_nodes()
    assert set(range(64)) <= set(dead)   # silent originals aged out
