"""Durable host plane (docs/resilience.md "Host-plane recovery"):
DurableStateStore crash-safety, wire-frame integrity (CRC + length
cap), generation-token session resume, chaos kill@/corrupt@ verbs,
the shared retry discipline, and host-plane incident forensics.
"""

import os
import socket
import struct
import time

import numpy as np
import pytest

from geomx_tpu.resilience.chaos import (ChaosEngine, ChaosSchedule,
                                        set_node_lifecycle_hook)
from geomx_tpu.resilience.durability import (DurabilityError,
                                             DurableStateStore)
from geomx_tpu.service import (GeoPSClient, GeoPSServer, GeoScheduler,
                               SchedulerClient)
from geomx_tpu.service.protocol import (FrameIntegrityError, Msg, MsgType,
                                        clear_corruption_overrides,
                                        max_frame_bytes,
                                        reseed_corrupt_rng,
                                        set_corruption_override,
                                        wire_crc_errors)
from geomx_tpu.service.retry import SeededBackoff, call_with_retries


# ---- DurableStateStore -----------------------------------------------------


def test_durable_store_snapshot_journal_roundtrip(tmp_path):
    s = DurableStateStore(str(tmp_path), "node")
    s.snapshot({"a": 1})
    s.append({"k": "r", "v": np.arange(4, dtype=np.float32)})
    s.append({"k": "r", "v": 2})
    s.close()
    s2 = DurableStateStore(str(tmp_path), "node")
    snap, recs = s2.load()
    assert snap == {"a": 1}
    assert len(recs) == 2
    np.testing.assert_array_equal(recs[0]["v"],
                                  np.arange(4, dtype=np.float32))
    # appends after a restart continue the sequence numbering
    s2.append({"k": "r", "v": 3})
    _, recs2 = s2.load()
    assert len(recs2) == 3


def test_durable_store_torn_tail_truncated(tmp_path):
    s = DurableStateStore(str(tmp_path), "node")
    s.append({"n": 1})
    s.append({"n": 2})
    s.close()
    path = os.path.join(str(tmp_path), "node.journal")
    blob = open(path, "rb").read()
    # crash mid-append: half a record's bytes at the tail
    with open(path, "wb") as f:
        f.write(blob + b"\x40\x00\x00\x00\xde\xad\xbe\xefpartial")
    snap, recs = DurableStateStore(str(tmp_path), "node").load()
    assert snap is None
    assert [r["n"] for r in recs] == [1, 2]  # tail truncated, not an error
    # ... and a flipped bit INSIDE a committed record stops replay there
    with open(path, "wb") as f:
        bad = bytearray(blob)
        bad[-3] ^= 1
        f.write(bytes(bad))
    _, recs = DurableStateStore(str(tmp_path), "node").load()
    assert [r["n"] for r in recs] == [1]


def test_durable_store_torn_tail_physically_truncated(tmp_path):
    """The double-crash case: crash #1 tears the tail; records appended
    after the restart must land where replay can SEE them — i.e. the
    torn bytes are truncated on load, not just skipped logically."""
    s = DurableStateStore(str(tmp_path), "node")
    s.append({"n": 1})
    s.close()
    path = os.path.join(str(tmp_path), "node.journal")
    with open(path, "ab") as f:
        f.write(b"\x40\x00\x00\x00torn-mid-append")   # crash #1
    s2 = DurableStateStore(str(tmp_path), "node")
    _, recs = s2.load()
    assert [r["n"] for r in recs] == [1]
    s2.append({"n": 2})   # post-restart round
    s2.close()            # crash #2 (no compact in between)
    _, recs = DurableStateStore(str(tmp_path), "node").load()
    assert [r["n"] for r in recs] == [1, 2]  # nothing silently lost


def test_reconnect_composes_with_p3_chunking_retaining_chunk_set():
    """PR 10 rejected reconnect+P3 loudly (the re-push retained only
    whole-tensor frames).  PR 11 retains a chunked round's FULL clean
    chunk set instead — construction succeeds and the retained entry
    holds every chunk frame (the mid-round restart replay is proven in
    tests/test_manyparty.py + the real-SIGKILL test in
    tests/test_recovery.py)."""
    import numpy as np

    from geomx_tpu.service import GeoPSServer
    srv = GeoPSServer(num_workers=2, mode="sync", accumulate=True).start()
    c = GeoPSClient(("127.0.0.1", srv.port), sender_id=0,
                    reconnect=True, p3_slice_elems=16)
    try:
        c.init("w", np.zeros(100, np.float32))
        c.push("w", np.ones(100, np.float32))   # 100 > 16: chunked
        rnd, frames, _prio = c._last_push["w"]
        assert rnd == 1 and len(frames) > 1     # the whole chunk set
    finally:
        c.close()
        srv.stop(forward=False)


def test_durable_store_compaction_covers_journal(tmp_path):
    s = DurableStateStore(str(tmp_path), "node")
    for i in range(5):
        s.append({"n": i})
    s.compact({"through": 4})
    s.append({"n": 5})
    s.close()
    snap, recs = DurableStateStore(str(tmp_path), "node").load()
    assert snap == {"through": 4}
    assert [r["n"] for r in recs] == [5]  # pre-compaction records folded


def test_durable_store_generation_bumps_per_start(tmp_path):
    s = DurableStateStore(str(tmp_path), "node")
    assert s.bump_generation() == 1
    assert DurableStateStore(str(tmp_path), "node").bump_generation() == 2
    assert DurableStateStore(str(tmp_path), "node").generation() == 2


def test_durable_store_bad_snapshot_is_loud(tmp_path):
    s = DurableStateStore(str(tmp_path), "node")
    s.snapshot({"a": 1})
    path = os.path.join(str(tmp_path), "node.snap")
    blob = bytearray(open(path, "rb").read())
    blob[-1] ^= 1  # disk damage, not a crash artifact: refuse to guess
    open(path, "wb").write(bytes(blob))
    with pytest.raises(DurabilityError):
        DurableStateStore(str(tmp_path), "node").load()


# ---- wire-frame integrity --------------------------------------------------


def test_frame_crc_detects_single_bit_flip():
    m = Msg(MsgType.PUSH, key="w", sender=1,
            meta={"rid": 5, "resend": True},
            array=np.arange(8, dtype=np.float32))
    frame = m.encode()
    out = Msg.decode(frame)
    np.testing.assert_array_equal(out.array, m.array)
    before = wire_crc_errors()
    for off in (2, 9, len(frame) - 1):  # crc byte, header, payload
        bad = bytearray(frame)
        bad[off] ^= 0x10
        with pytest.raises(FrameIntegrityError):
            Msg.decode(bytes(bad))
    assert wire_crc_errors() - before == 3


def test_frame_unknown_version_rejected():
    """No bare-frame fallback: a stripped prelude (pre-integrity peer,
    or a corrupted version byte) is an integrity rejection, not a
    guess — the two formats would otherwise be ambiguous whenever a
    header length's low byte collided with the version value."""
    m = Msg(MsgType.PULL, key="w", sender=0, meta={"rid": 1})
    framed = m.encode()
    before = wire_crc_errors()
    with pytest.raises(FrameIntegrityError, match="version"):
        Msg.decode(framed[5:])
    with pytest.raises(FrameIntegrityError):
        Msg.decode(b"")
    assert wire_crc_errors() - before == 2


def test_frame_length_cap_rejects_before_allocation(monkeypatch):
    from geomx_tpu.service import protocol
    monkeypatch.setenv("GEOMX_MAX_FRAME_BYTES", "4096")
    protocol.reset_frame_limit_cache()
    try:
        assert max_frame_bytes() == 4096
        a, b = socket.socketpair()
        try:
            a.sendall(struct.pack("<I", 1 << 31))
            before = wire_crc_errors()
            with pytest.raises(FrameIntegrityError):
                protocol.recv_frame(b)
            assert wire_crc_errors() - before == 1
        finally:
            a.close()
            b.close()
    finally:
        protocol.reset_frame_limit_cache()


def test_oversized_frame_drops_connection_server_survives():
    srv = GeoPSServer(num_workers=1, mode="sync", accumulate=True).start()
    try:
        evil = socket.create_connection(("127.0.0.1", srv.port),
                                        timeout=5.0)
        evil.settimeout(5.0)
        evil.sendall(struct.pack("<I", (max_frame_bytes() + 1)
                                 & 0xFFFFFFFF))
        assert evil.recv(1) == b""  # server closed the connection
        evil.close()
        c = GeoPSClient(("127.0.0.1", srv.port), sender_id=0)
        c.init("w", np.zeros(8, np.float32))
        c.push("w", np.ones(8, np.float32))
        np.testing.assert_allclose(c.pull("w"), 1.0)  # tier still serves
        c.stop_server()
        c.close()
    finally:
        srv.join(5)


# ---- chaos grammar: kill@ / restart@ / corrupt@ ---------------------------


def test_chaos_kill_restart_corrupt_grammar_roundtrip():
    spec = ("seed=9;kill@4:node=server,restart_after=2;"
            "kill@8:node=scheduler;restart@9:node=scheduler;"
            "corrupt@2:party=0,rate=40,steps=3")
    s = ChaosSchedule.from_spec(spec)
    assert ChaosSchedule.from_spec(s.spec()).events == s.events
    kinds = [(e.step, e.kind) for e in s.events]
    assert (6, "restart") in kinds      # restart_after expanded
    assert (5, "corrupt_clear") in kinds
    with pytest.raises(ValueError, match="node="):
        ChaosSchedule.from_spec("kill@1:node=worker")
    with pytest.raises(ValueError, match="rate"):
        ChaosSchedule.from_spec("corrupt@1:party=0,rate=200")


def test_chaos_engine_drives_lifecycle_hook_and_corruption():
    from geomx_tpu.service import protocol
    calls = []
    set_node_lifecycle_hook(lambda a, n: calls.append((a, n)))
    try:
        s = ChaosSchedule.from_spec(
            "seed=3;corrupt@1:party=2,rate=25,steps=2;"
            "kill@2:node=server,restart_after=1")
        with ChaosEngine(s, controller=None) as eng:
            eng.tick(1)
            assert protocol._corrupt_rates == {2: 25}
            eng.tick(3)
        assert calls == [("kill", "server"), ("restart", "server")]
        assert protocol._corrupt_rates == {}  # close() cleared it
    finally:
        set_node_lifecycle_hook(None)


def test_chaos_kill_without_hook_is_loud():
    s = ChaosSchedule.from_spec("kill@1:node=server")
    with ChaosEngine(s, controller=None) as eng:
        with pytest.raises(ValueError, match="lifecycle hook"):
            eng.tick(1)


def test_corruption_detected_and_retried_transparently():
    """100% first-transmission corruption: every frame is rejected by
    the wire-CRC gate, the connection drops, and the session-resume +
    resend path re-delivers the CLEAN retained copy — values stay
    exact, nothing crashes, the counter counts."""
    srv = GeoPSServer(num_workers=1, mode="sync", accumulate=True).start()
    c = GeoPSClient(("127.0.0.1", srv.port), sender_id=0, reconnect=True)
    try:
        c.init("w", np.zeros(16, np.float32))
        reseed_corrupt_rng(7)
        set_corruption_override(0, 100)
        before = wire_crc_errors()
        for step in range(3):
            c.push("w", np.ones(16, np.float32))
            np.testing.assert_allclose(c.pull("w", timeout=30.0),
                                       float(step + 1))
        assert wire_crc_errors() - before >= 3
    finally:
        clear_corruption_overrides()
        c.stop_server()
        c.close()
        srv.join(5)


# ---- durable server restart + session resume ------------------------------


def test_server_restart_replays_durable_state(tmp_path):
    srv = GeoPSServer(num_workers=1, mode="sync", accumulate=True,
                      durable_dir=str(tmp_path), durable_name="g").start()
    port = srv.port
    c = GeoPSClient(("127.0.0.1", port), sender_id=0)
    c.init("w", np.zeros(8, np.float32))
    c.push("w", np.full(8, 3.0, np.float32))
    np.testing.assert_allclose(c.pull("w"), 3.0)
    c.close()
    srv.crash()
    srv2 = GeoPSServer(num_workers=1, mode="sync", accumulate=True,
                       durable_dir=str(tmp_path), durable_name="g",
                       port=port).start()
    assert srv2.generation == 2
    c2 = GeoPSClient(("127.0.0.1", port), sender_id=0)
    np.testing.assert_allclose(c2.pull("w"), 3.0)   # store replayed
    assert c2.recover()["w"] == 1                    # rounds replayed
    c2.push("w", np.full(8, 1.0, np.float32))
    np.testing.assert_allclose(c2.pull("w"), 4.0)
    c2.stop_server()
    c2.close()
    srv2.join(5)


def test_session_resume_repushes_inflight_round(tmp_path):
    """Mid-round crash: A pushed round 2 (ACKed, merged in memory only),
    B had not.  The restart discards the partial merge; A's resume
    handshake detects the generation change and re-pushes round 2 from
    the retained frame, B pushes normally — the final aggregate is
    exact, with no loss and no double-merge."""
    srv = GeoPSServer(num_workers=2, mode="sync", accumulate=True,
                      durable_dir=str(tmp_path), durable_name="g").start()
    port = srv.port
    ca = GeoPSClient(("127.0.0.1", port), sender_id=0, reconnect=True)
    cb = GeoPSClient(("127.0.0.1", port), sender_id=1, reconnect=True)
    try:
        n = 32
        for c in (ca, cb):
            c.init("w", np.zeros(n, np.float32))
        ca.push("w", np.full(n, 1.0, np.float32))
        cb.push("w", np.full(n, 2.0, np.float32))
        np.testing.assert_allclose(ca.pull("w"), 3.0)
        ca.push("w", np.full(n, 5.0, np.float32))  # round 2, A only
        time.sleep(0.2)                            # let it merge
        srv.crash()
        srv2 = GeoPSServer(num_workers=2, mode="sync", accumulate=True,
                           durable_dir=str(tmp_path), durable_name="g",
                           port=port).start()
        try:
            cb.push("w", np.full(n, 2.0, np.float32))  # round 2, B
            np.testing.assert_allclose(cb.pull("w", timeout=60.0), 10.0)
            np.testing.assert_allclose(ca.pull("w", timeout=60.0), 10.0)
        finally:
            ca.stop_server()
            srv2.join(5)
    finally:
        ca.close()
        cb.close()


def test_durable_server_optimizer_state_survives_restart(tmp_path):
    """Server-side optax SGD-momentum: the restarted server applies the
    SAME update a never-crashed server would (optimizer state rides the
    round journal)."""
    def run(crash_between):
        d = tmp_path / ("opt_crash" if crash_between else "opt_base")
        srv = GeoPSServer(num_workers=1, mode="sync",
                          durable_dir=str(d), durable_name="g").start()
        port = srv.port
        c = GeoPSClient(("127.0.0.1", port), sender_id=0)
        c.set_optimizer("momentum", learning_rate=0.1)
        c.init("w", np.full(4, 1.0, np.float32))
        c.push("w", np.full(4, 1.0, np.float32))
        c.pull("w")
        if crash_between:
            c.close()
            srv.crash()
            srv = GeoPSServer(num_workers=1, mode="sync",
                              durable_dir=str(d), durable_name="g",
                              port=port).start()
            c = GeoPSClient(("127.0.0.1", port), sender_id=0)
            # the worker-restart discipline: resume round ids from the
            # server so the next push is not absorbed as a replay
            assert c.recover()["w"] == 1
        c.push("w", np.full(4, 1.0, np.float32))
        out = np.asarray(c.pull("w"))
        c.stop_server()
        c.close()
        srv.join(5)
        return out
    np.testing.assert_array_equal(run(False), run(True))


# ---- durable scheduler restart --------------------------------------------


def test_scheduler_restart_keeps_ids_epoch_and_grace(tmp_path):
    sch = GeoScheduler(durable_dir=str(tmp_path)).start()
    port = sch.port
    sc = SchedulerClient(("127.0.0.1", port))
    sc.register("worker", tag="0.0")
    wid = sc.node_id
    epoch0 = sc.roster_epoch
    sc.heartbeat()
    sch.crash()
    time.sleep(0.2)
    sch2 = GeoScheduler(durable_dir=str(tmp_path), port=port,
                        heartbeat_timeout=0.2,
                        restart_grace_s=30.0).start()
    try:
        assert sch2.generation == 2
        assert sch2.in_restart_grace()
        sc2 = SchedulerClient(("127.0.0.1", port))
        meta = sc2.register("worker", tag="0.0")
        assert sc2.node_id == wid            # id survived the restart
        assert meta["is_recovery"] is True
        assert sc2.roster_epoch > epoch0     # epoch continued, not reset
        assert sc2.dead_nodes() == []        # grace holds the list shut
        # the OLD client's severed socket: its rpc retries through a
        # re-dial and sees the restart via the generation token
        assert sc.dead_nodes() == []
        assert sc.saw_scheduler_restart is True
        health = sch2.health_snapshot()
        assert health["restart_grace"] is True
        assert health["generation"] == 2
        sc2.close()
    finally:
        sc.close()
        sch2.stop()


# ---- retry discipline ------------------------------------------------------


def test_seeded_backoff_is_deterministic_and_bounded():
    a = [SeededBackoff(seed=5, base_s=0.1, max_s=1.0).next()
         for _ in range(1)]
    b1 = SeededBackoff(seed=5, base_s=0.1, max_s=1.0)
    b2 = SeededBackoff(seed=5, base_s=0.1, max_s=1.0)
    seq1 = [b1.next() for _ in range(6)]
    seq2 = [b2.next() for _ in range(6)]
    assert seq1 == seq2                      # same seed, same delays
    assert a[0] == seq1[0]
    assert all(d <= 1.0 for d in seq1)       # jitter only shrinks
    assert seq1 != [SeededBackoff(seed=6, base_s=0.1, max_s=1.0).next()
                    for _ in range(6)]
    with pytest.raises(ValueError):
        SeededBackoff(jitter=1.5)


def test_call_with_retries_counts_and_raises():
    from geomx_tpu.telemetry import get_registry
    calls = []
    slept = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("transient")
        return "ok"

    out = call_with_retries("test_op", flaky, attempts=5,
                            backoff=SeededBackoff(seed=1),
                            sleep=slept.append)
    assert out == "ok" and len(calls) == 3 and len(slept) == 2
    fam = get_registry().get("geomx_rpc_retries_total")
    assert fam.labels(op="test_op").value >= 2
    with pytest.raises(OSError):
        call_with_retries("test_op", lambda: (_ for _ in ()).throw(
            OSError("always")), attempts=2, sleep=lambda _s: None)


# ---- host-plane incidents in the flight recorder --------------------------


def test_host_incidents_reach_flight_bundle(tmp_path):
    from geomx_tpu.telemetry import get_registry
    from geomx_tpu.telemetry.flight import (FlightRecorder,
                                            install_incident_recorder,
                                            notify_host_incident,
                                            uninstall_incident_recorder)
    rec = FlightRecorder(capacity=8, dump_dir=str(tmp_path))
    install_incident_recorder(rec)
    try:
        notify_host_incident("server_restart", rank=0, generation=2)
        notify_host_incident("wire_crc_error", reason="crc")
        assert [i["kind"] for i in rec.incidents()] == [
            "server_restart", "wire_crc_error"]
        assert rec.incidents()[0]["detail"]["generation"] == 2
        fam = get_registry().get("geomx_host_incidents_total")
        assert fam.labels(kind="server_restart").value >= 1
        # the incidents ride the forensics bundle next to the ring
        import json
        path = rec.dump([], {"step": 1, "probes": {}})
        bundle = json.load(open(path))
        assert [i["kind"] for i in bundle["incidents"]] == [
            "server_restart", "wire_crc_error"]
    finally:
        uninstall_incident_recorder(rec)


def test_server_restart_publishes_incident(tmp_path):
    from geomx_tpu.telemetry import get_registry
    srv = GeoPSServer(num_workers=1, mode="sync",
                      durable_dir=str(tmp_path), durable_name="g")
    srv.crash()
    srv2 = GeoPSServer(num_workers=1, mode="sync",
                       durable_dir=str(tmp_path), durable_name="g")
    srv2.crash()
    reg = get_registry()
    assert reg.get("geomx_host_restarts_total").labels(
        node="server_r0").value >= 1
    assert reg.get("geomx_host_generation").labels(
        node="server_r0").value == 2
    assert reg.get("geomx_host_incidents_total").labels(
        kind="server_restart").value >= 1
