"""Host-side PS service tests.

Modelled on the reference's ps-lite micro-tests
(3rdparty/ps-lite/tests/test_kv_app.cc:1-62 — N workers push repeatedly,
assert pulls equal the expected aggregate), with the multi-node topology
simulated by threads on localhost exactly as the reference's tests/
local.sh simulates it with processes.
"""

import threading
import time

import numpy as np
import pytest

from geomx_tpu.service import GeoPSClient, GeoPSServer


def test_single_tier_sync_push_pull():
    """test_kv_app parity: repeated synchronized pushes, pull == sum."""
    server = GeoPSServer(num_workers=3, mode="sync", accumulate=True).start()
    clients = [GeoPSClient(("127.0.0.1", server.port), sender_id=i)
               for i in range(3)]
    n = 1000
    for c in clients:
        c.init("w", np.zeros(n, np.float32))
    repeat = 10
    errs = []

    def worker(c, wid):
        try:
            for r in range(repeat):
                c.push("w", np.full(n, 1.0 + wid, np.float32))
                out = c.pull("w")
                expect = (r + 1) * (1.0 + 2.0 + 3.0)
                if not np.allclose(out, expect):
                    errs.append((wid, r, out[0], expect))
        except Exception as e:
            errs.append((wid, repr(e)))

    threads = [threading.Thread(target=worker, args=(c, i))
               for i, c in enumerate(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errs, errs
    for c in clients:
        c.stop_server()
        c.close()


def test_barrier_blocks_until_all_enter():
    server = GeoPSServer(num_workers=2).start()
    c0 = GeoPSClient(("127.0.0.1", server.port), sender_id=0)
    c1 = GeoPSClient(("127.0.0.1", server.port), sender_id=1)
    order = []

    def late():
        time.sleep(0.2)
        order.append("enter1")
        c1.barrier()

    t = threading.Thread(target=late)
    t.start()
    order.append("enter0")
    c0.barrier()
    order.append("released")
    t.join(timeout=10)
    assert order == ["enter0", "enter1", "released"]
    server.stop()


def test_two_tier_hips_relay():
    """2 parties x 2 workers + global server: the full HiPS dataflow
    (worker push -> local merge -> global merge -> pull back down)."""
    gs = GeoPSServer(num_workers=2, mode="sync").start()  # 2 global workers
    locals_ = [GeoPSServer(num_workers=2, mode="sync",
                           global_addr=("127.0.0.1", gs.port)).start()
               for _ in range(2)]
    n = 256
    workers = []
    for p, ls in enumerate(locals_):
        for w in range(2):
            workers.append((p, GeoPSClient(("127.0.0.1", ls.port),
                                           sender_id=w)))
    # local INIT must also register the key at the global tier: the local
    # server relays on first merge, so init globals first via a direct client
    ginit = GeoPSClient(("127.0.0.1", gs.port), sender_id=99)
    ginit.init("w", np.zeros(n, np.float32))
    for _, c in workers:
        c.init("w", np.zeros(n, np.float32))

    results = {}
    errs = []

    def run(p, wid, c):
        try:
            c.push("w", np.full(n, 1.0, np.float32))
            results[(p, wid)] = c.pull("w")
        except Exception as e:
            errs.append(repr(e))

    threads = [threading.Thread(target=run, args=(p, i, c))
               for i, (p, c) in enumerate(workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errs, errs
    # each party merges 2 pushes of 1.0 -> 2.0; global merges 2 parties -> 4.0
    for k, v in results.items():
        np.testing.assert_allclose(v, 4.0, err_msg=str(k))
    for ls in locals_:
        ls.stop()
    gs.stop()


def test_async_mode_with_optimizer():
    """dist_async tier: pushes apply on arrival through the server-side
    optimizer (reference DataHandleAsyncDefault + python updater)."""
    server = GeoPSServer(num_workers=2, mode="async").start()
    c = GeoPSClient(("127.0.0.1", server.port), sender_id=0)
    c.init("w", np.zeros(4, np.float32))
    c.set_optimizer("sgd", learning_rate=0.1)
    c.push("w", np.ones(4, np.float32))
    np.testing.assert_allclose(c.pull("w"), -0.1, rtol=1e-6)
    c.push("w", np.ones(4, np.float32))
    np.testing.assert_allclose(c.pull("w"), -0.2, rtol=1e-6)
    server.stop()


def test_bsc_compressed_relay():
    """Local -> global hop with Bi-Sparse compression: sparse payload on
    the wire, spikes survive, server-side decompression."""
    gs = GeoPSServer(num_workers=1, mode="sync").start()
    ls = GeoPSServer(num_workers=1, mode="sync",
                     global_addr=("127.0.0.1", gs.port),
                     compression="bsc,0.01").start()
    n = 4096
    ginit = GeoPSClient(("127.0.0.1", gs.port), sender_id=9)
    ginit.init("w", np.zeros(n, np.float32))
    c = GeoPSClient(("127.0.0.1", ls.port), sender_id=0)
    c.init("w", np.zeros(n, np.float32))
    g = np.random.RandomState(0).normal(0, 1e-3, n).astype(np.float32)
    g[123] = 9.0
    g[456] = -7.0
    c.push("w", g)
    out = c.pull("w")
    assert out[123] == pytest.approx(9.0, abs=0.01)
    assert out[456] == pytest.approx(-7.0, abs=0.01)
    assert (out != 0).sum() <= 2 * int(np.ceil(n * 0.01))
    ls.stop()
    gs.stop()


def test_priority_ordering_on_the_wire():
    """P3: queued pushes leave in priority order (front layers first)."""
    server = GeoPSServer(num_workers=1, mode="async").start()
    arrivals = []
    orig = server._handle_push

    def spy(conn, msg):
        arrivals.append(msg.key)
        return orig(conn, msg)

    server._handle_push = spy
    c = GeoPSClient(("127.0.0.1", server.port), sender_id=0)
    for i in range(4):
        c.init(f"layer{i}", np.zeros(8, np.float32))
    # stall the sender so all pushes queue, then release.  The sender pops
    # one message before blocking on the write lock, so feed it a
    # sacrificial max-priority heartbeat first; the 4 data pushes then all
    # sit in the queue together and must leave in priority order.
    from geomx_tpu.service.protocol import Msg, MsgType
    with c._wlock:
        c._submit(Msg(MsgType.HEARTBEAT), priority=10)
        time.sleep(0.05)
        rids = [c.push_async(f"layer{i}", np.ones(8, np.float32),
                             priority=-i)
                for i in (3, 1, 2, 0)]
        time.sleep(0.1)
    for r in rids:
        c.wait(r, timeout=10)
    assert arrivals == ["layer0", "layer1", "layer2", "layer3"]
    server.stop()


def test_dead_node_detection():
    server = GeoPSServer(num_workers=1, heartbeat_timeout=0.2).start()
    c = GeoPSClient(("127.0.0.1", server.port), sender_id=5)
    monitor = GeoPSClient(("127.0.0.1", server.port), sender_id=-1)
    c.heartbeat()
    assert monitor.num_dead_nodes() == 0
    time.sleep(0.3)
    assert monitor.num_dead_nodes() == 1  # node 5 went silent
    c.heartbeat()
    assert monitor.num_dead_nodes() == 0  # recovery clears it (is_recovery)
    server.stop()


def test_error_reply_for_unknown_key():
    server = GeoPSServer(num_workers=1).start()
    c = GeoPSClient(("127.0.0.1", server.port))
    with pytest.raises(RuntimeError, match="no key"):
        c.pull("missing")
    server.stop()


def test_wire_header_rejects_code_loading_pickles():
    """The wire header decoder must refuse pickles that resolve globals —
    that is the remote-code-execution vector once servers bind
    non-loopback interfaces (GEOMX_PS_BIND_HOST=0.0.0.0)."""
    import pickle
    import struct

    from geomx_tpu.service.protocol import Msg, MsgType

    # round trip of a legitimate primitive header still works
    m = Msg(MsgType.PUSH, key="w", sender=3,
            meta={"rid": 7, "resend": True, "nested": [1, 2.5, ("a", None)]},
            array=np.arange(6, dtype=np.float32).reshape(2, 3))
    out = Msg.decode(m.encode())
    assert out.meta == m.meta and np.array_equal(out.array, m.array)

    # a crafted header that would import a callable must be rejected
    # even when wrapped in a perfectly valid integrity prelude: the CRC
    # authenticates nothing — the primitives-only unpickler is the gate
    import zlib

    from geomx_tpu.service.protocol import FRAME_VERSION
    evil = pickle.dumps({"t": 1, "k": None, "s": 0,
                         "m": {"f": np.frombuffer}}, protocol=4)
    body = struct.pack("<I", len(evil)) + evil
    frame = bytes((FRAME_VERSION,)) + struct.pack(
        "<I", zlib.crc32(body)) + body
    with pytest.raises(pickle.UnpicklingError):
        Msg.decode(frame)


def test_tsengine_autopull_distribution():
    """TSEngine AutoPull: with ENABLE_INTRA_TS semantics the server pushes
    each round's fresh value to registered workers in scheduler-chosen
    order and records throughput measurements (reference DefaultAutoPull /
    AutoPullUpdate, kvstore_dist_server.h:1372-1395, kv_app.h:586-691)."""
    server = GeoPSServer(port=0, num_workers=2, mode="sync",
                         accumulate=True, auto_pull=True).start()
    addr = ("127.0.0.1", server.port)
    try:
        c0 = GeoPSClient(addr, sender_id=0, auto_pull=True)
        c1 = GeoPSClient(addr, sender_id=1, auto_pull=True)
        c0.init("w", np.zeros(4, np.float32))

        for rnd in range(1, 4):
            c0.push_async("w", np.ones(4, np.float32))
            c1.push_async("w", np.ones(4, np.float32))
            # both workers receive the round's value WITHOUT pulling
            v0 = c0.auto_pull("w", min_version=rnd, timeout=30)
            v1 = c1.auto_pull("w", min_version=rnd, timeout=30)
            np.testing.assert_allclose(v0, 2.0 * rnd)
            np.testing.assert_allclose(v1, 2.0 * rnd)

        # the scheduler accumulated real throughput measurements.
        # auto_pull returns when the VALUE lands; the distributor's
        # throughput report (which advances sched.iters) trails it on
        # another thread — wait it out instead of racing it.
        deadline = time.time() + 5.0
        while server.ts_sched.iters < 3 and time.time() < deadline:
            time.sleep(0.05)
        measured = [t for row in server.ts_sched.A for t in row
                    if t is not None]
        assert measured and all(t > 0 for t in measured)
        assert server.ts_sched.iters >= 3
        c0.close()
        c1.close()
    finally:
        server.stop()


def test_autopull_reconnect_reclaims_slot_and_dead_client_fails_fast():
    server = GeoPSServer(port=0, num_workers=2, mode="sync",
                         accumulate=True, auto_pull=True).start()
    addr = ("127.0.0.1", server.port)
    try:
        c0 = GeoPSClient(addr, sender_id=0, auto_pull=True)
        c1 = GeoPSClient(addr, sender_id=1, auto_pull=True)
        c0.init("w", np.zeros(2, np.float32))
        c1.close()  # worker 1 dies...
        c1b = GeoPSClient(addr, sender_id=1, auto_pull=True)  # ...restarts
        c0.push_async("w", np.ones(2, np.float32))
        c1b.push_async("w", np.ones(2, np.float32))
        # the reconnected client reclaimed slot 1 and receives the round
        np.testing.assert_allclose(
            c1b.auto_pull("w", min_version=1, timeout=30), 2.0)
        # a third distinct sender overflows the table with a clear error
        with pytest.raises(RuntimeError, match="autopull table full"):
            GeoPSClient(addr, sender_id=7, auto_pull=True)
        c1b.close()
    finally:
        server.stop()

    # the still-connected client's auto_pull fails fast on server death
    # (the recv loop wakes autopull waiters) instead of burning its timeout
    t0 = time.time()
    with pytest.raises(ConnectionError):
        c0.auto_pull("w", min_version=99, timeout=30)
    assert time.time() - t0 < 10
    c0.close()


def test_hfa_k2_reduces_global_relays():
    """A local server with hfa_k2=2 completes 4 local rounds but crosses
    the WAN only twice, and — like the reference, which calls ApplyUpdates
    every round (kvstore_dist_server.h:1326) — workers pull the *fresh*
    party average even on skip rounds; WAN hops carry the milestone delta
    (kvstore_dist_server.h:988-1017, 1334-1338)."""
    glob = GeoPSServer(port=0, num_workers=1, mode="sync",
                       accumulate=True).start()
    local = GeoPSServer(port=0, num_workers=1, mode="sync",
                        global_addr=("127.0.0.1", glob.port),
                        global_sender_id=1000, hfa_k2=2,
                        num_global_workers=1).start()
    try:
        c = GeoPSClient(("127.0.0.1", local.port), sender_id=0)
        c.init("w", np.zeros(3, np.float32))
        for i in range(1, 5):
            # HFA workers push party-averaged *parameters*
            c.push("w", np.full(3, float(i), np.float32))
            # every round — including WAN-skip rounds — the pull reflects
            # this round's party average (ADVICE r1: value must not freeze
            # for K2-1 rounds)
            np.testing.assert_allclose(c.pull("w"), float(i))
        assert glob._store["w"].round == 2        # only 2 WAN crossings
        # the global store accumulated both milestone deltas onto the
        # init: 0 + (2-0)/1 + (4-2)/1 = the authoritative params
        np.testing.assert_allclose(glob._store["w"].value, 4.0)
        # milestone rebased to the agreed params: no drift across parties
        np.testing.assert_allclose(local._store["w"].milestone, 4.0)
        c.close()
    finally:
        local.stop()
        glob.stop()


def test_straggler_party_does_not_stall_local_server():
    """ADVICE r2 #3 regression: while party A's relay is parked at the
    global tier waiting for a straggler party B, A's local server must
    keep serving heartbeats, commands, and OTHER keys' full rounds (the
    WAN hop runs on the relay thread, not under the server lock)."""
    import numpy as np

    gsrv = GeoPSServer(num_workers=2, mode="sync", rank=0).start()
    la = GeoPSServer(num_workers=1, mode="sync",
                     global_addr=("127.0.0.1", gsrv.port),
                     global_sender_id=1000, rank=1).start()
    lb = GeoPSServer(num_workers=1, mode="sync",
                     global_addr=("127.0.0.1", gsrv.port),
                     global_sender_id=1001, rank=2).start()
    ca = GeoPSClient(("127.0.0.1", la.port), sender_id=0)
    cb = GeoPSClient(("127.0.0.1", lb.port), sender_id=0)
    n = 64
    for c in (ca, cb):
        c.init("slow", np.zeros(n, np.float32))
        c.init("fast", np.zeros(n, np.float32))

    # A pushes "slow"; its relay blocks at the global tier until B joins
    t_slow = ca.push_async("slow", np.full(n, 1.0, np.float32))
    ca.wait(t_slow)          # local merge ACKs immediately
    time.sleep(0.3)          # relay thread is now parked at the WAN

    # while parked: heartbeats, commands and a full OTHER-key round on A
    t0 = time.monotonic()
    ca.heartbeat()
    assert ca.num_dead_nodes(timeout=60) == 0
    ca.push("fast", np.full(n, 5.0, np.float32))
    cb.push("fast", np.full(n, 7.0, np.float32))
    out = ca.pull("fast", timeout=30.0)
    assert time.monotonic() - t0 < 10.0, "local server stalled by straggler"
    assert out.shape == (n,)

    # the straggler arrives; the parked round completes correctly
    cb.push("slow", np.full(n, 2.0, np.float32))
    np.testing.assert_allclose(ca.pull("slow", timeout=30.0),
                               cb.pull("slow", timeout=30.0))
    for c in (ca, cb):
        c.stop_server()
        c.close()


def test_async_relay_runs_off_lock_and_off_serve_thread():
    """ADVICE r3 #3 regression: in ASYNC mode the WAN push-through must
    run on the relay shard, not inline under the server lock — while
    party A's relay of "slow" is parked at a sync global tier waiting for
    party B, A's server must keep answering heartbeats, commands, and a
    full round of an OTHER key from the SAME client connection.  The
    pusher's ACK is deferred until the relayed value installs."""
    gsrv = GeoPSServer(num_workers=2, mode="sync", rank=0).start()
    la = GeoPSServer(num_workers=1, mode="async",
                     global_addr=("127.0.0.1", gsrv.port),
                     global_sender_id=1000, rank=1).start()
    lb = GeoPSServer(num_workers=1, mode="async",
                     global_addr=("127.0.0.1", gsrv.port),
                     global_sender_id=1001, rank=2).start()
    ca = GeoPSClient(("127.0.0.1", la.port), sender_id=0)
    cb = GeoPSClient(("127.0.0.1", lb.port), sender_id=0)
    n = 64
    # "slow" and "fast" hash to different relay shards (5 and 4 of 8), so
    # the parked "slow" relay cannot FIFO-block the "fast" one
    for c in (ca, cb):
        c.init("slow", np.zeros(n, np.float32))
        c.init("fast", np.zeros(n, np.float32))

    # A's push of "slow" relays immediately (async mode) and parks at the
    # sync global tier until B contributes; the ACK is deferred
    t_slow = ca.push_async("slow", np.full(n, 1.0, np.float32))
    time.sleep(0.3)

    # while parked: the SAME connection keeps being served
    t0 = time.monotonic()
    ca.heartbeat()
    assert ca.num_dead_nodes(timeout=60) == 0
    t_fa = ca.push_async("fast", np.full(n, 5.0, np.float32))
    t_fb = cb.push_async("fast", np.full(n, 7.0, np.float32))
    ca.wait(t_fa, timeout=30.0)
    cb.wait(t_fb, timeout=30.0)
    out = ca.pull("fast", timeout=30.0)
    assert time.monotonic() - t0 < 10.0, "async relay stalled the server"
    np.testing.assert_allclose(out, 12.0)

    # the straggler arrives: the parked push ACKs and both parties agree
    cb.push("slow", np.full(n, 2.0, np.float32), meta=None)
    ca.wait(t_slow, timeout=30.0)
    np.testing.assert_allclose(ca.pull("slow", timeout=30.0),
                               cb.pull("slow", timeout=30.0))
    for c in (ca, cb):
        c.stop_server()
        c.close()


def test_wire_stats_and_verbose_logging(monkeypatch, capfd):
    """Van-parity observability (reference van.h:182-183 byte counters,
    postoffice.h:237 PS_VERBOSE): the server reports its sent/received
    byte+message counters via the wire_stats command, and PS_VERBOSE>=2
    logs each message."""
    from geomx_tpu.service.protocol import (reset_verbose_cache,
                                            wire_stats)

    monkeypatch.setenv("GEOMX_PS_VERBOSE", "2")
    reset_verbose_cache()  # the level is cached off the hot path
    try:
        _run_wire_stats_body(capfd, wire_stats)
    finally:
        # clear the env BEFORE resetting the cache: a late ACK on a daemon
        # thread would otherwise re-read PS_VERBOSE=2 (monkeypatch only
        # reverts at teardown) and leak wire logs into later tests
        monkeypatch.delenv("GEOMX_PS_VERBOSE", raising=False)
        reset_verbose_cache()


def _run_wire_stats_body(capfd, wire_stats):
    before = wire_stats.snapshot()
    server = GeoPSServer(num_workers=1, mode="sync").start()
    c = GeoPSClient(("127.0.0.1", server.port), sender_id=0)
    n = 256
    c.init("w", np.zeros(n, np.float32))
    c.push("w", np.ones(n, np.float32))
    out = c.pull("w")
    assert out.shape == (n,)

    stats = c.wire_stats()
    # the server received at least init+push+pull and answered each; the
    # push/pull payloads alone are > n*4 bytes each way
    assert stats["msgs_received"] >= 3
    assert stats["bytes_received"] - before["bytes_received"] > n * 4
    assert stats["bytes_sent"] - before["bytes_sent"] > n * 4
    err = capfd.readouterr().err
    assert "[geomx-wire]" in err and "PUSH" in err
    c.stop_server()
    c.close()


def test_join_gates_on_stop_forward_completion(monkeypatch):
    """Regression (r5 shutdown race): stop() runs on a daemon handler
    thread when the last worker STOP arrives; join() returning as soon
    as the listen socket closed let the MAIN thread exit the process
    with the STOP-forward loop half done, stranding a global server.
    join() must not return before the forward to the global tier has
    completed — even when that forward is slow."""
    gs = GeoPSServer(num_workers=1, mode="sync").start()
    ls = GeoPSServer(num_workers=1, mode="sync",
                     global_addr=("127.0.0.1", gs.port),
                     global_sender_id=1000).start()

    real_stop = GeoPSClient.stop_server

    def slow_stop(self):
        if self.sender_id >= 1000:  # only the local->global relay leg
            time.sleep(1.0)         # a slow WAN: the race window, widened
        return real_stop(self)

    monkeypatch.setattr(GeoPSClient, "stop_server", slow_stop)

    c = GeoPSClient(("127.0.0.1", ls.port), sender_id=0)
    c.init("w", np.zeros(16, np.float32))
    c.stop_server()   # ACKed BEFORE ls begins its slow forward
    t0 = time.monotonic()
    ls.join(timeout=20.0)
    waited = time.monotonic() - t0
    # join must have covered the slow forward (>= the injected delay)
    assert waited >= 0.9, waited
    # and the global actually received its stop: it shuts down too
    gs.join(timeout=10.0)
    assert gs._stops >= 1
    c.close()


def test_ps_plane_throughput_tool():
    """tools/bench_service.py drives W concurrent clients through the
    sync merge barrier and reports goodput — the PS plane's perf story
    (the chip benchmark covers only the SPMD plane)."""
    import importlib.util
    import os as _os
    spec = importlib.util.spec_from_file_location(
        "bench_service", _os.path.join(
            _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
            "tools", "bench_service.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rec = mod.run(mb=0.5, workers=2, rounds=3)
    assert rec["push_pull_mb_s"] > 0
    assert rec["workers"] == 2 and rec["rounds"] == 3
    # message accounting: at least push+pull per worker per round (the
    # merge VALUE itself is asserted inside the tool's workers)
    assert rec["server_msgs"] >= 2 * 2 * 3
