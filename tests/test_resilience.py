"""Elastic resilience subsystem (resilience/): membership epochs,
degraded-mode WAN sync, re-admission catch-up, and deterministic chaos.

The contract under test: a dead party's shard is EXCLUDED from the
dc-tier aggregate and the mean renormalizes over survivors bit-exactly
(inside one program the masked psum adds exact zeros); the membership
epoch is a versioned, recompile-boundary property (the Trainer swaps a
cached step program per mask); compressor residuals and pipeline
double-buffers follow the documented reset/carry policy across a
blackout/re-admit cycle; and a seeded chaos schedule reproduces the
same failure scenario run to run.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from geomx_tpu.models import GeoCNN
from geomx_tpu.parallel.collectives import shard_map_compat
from geomx_tpu.resilience import (ChaosEngine, ChaosEvent, ChaosSchedule,
                                  PartyLivenessController)
from geomx_tpu.resilience.liveness import pack_catchup, unpack_catchup
from geomx_tpu.sync import FSA, HFA, MixedSync, PipelinedSync
from geomx_tpu.topology import HiPSTopology, normalize_live_mask
from geomx_tpu.train import Trainer
from geomx_tpu.train.state import unreplicate_tree
from geomx_tpu.utils.heartbeat import HeartbeatMonitor


# --------------------------------------------------------------------------
# PartyLivenessController: versioned membership epochs
# --------------------------------------------------------------------------

def test_controller_publishes_versioned_epochs():
    c = PartyLivenessController(num_parties=3)
    e0 = c.epoch
    assert e0.version == 0 and e0.all_live and e0.num_live == 3
    seen = []
    c.subscribe(seen.append)

    e1 = c.mark_dead(1)
    assert e1.version == 1 and e1.live_mask == (True, False, True)
    assert e1.num_live == 2 and e1.renorm_weight == 0.5
    assert e1.live_parties() == [0, 2]
    # idempotent transition: no version bump, no callback
    e1b = c.mark_dead(1)
    assert e1b.version == 1
    e2 = c.mark_live(1)
    assert e2.version == 2 and e2.all_live
    assert [e.version for e in seen] == [1, 2]


def test_controller_min_live_floor():
    c = PartyLivenessController(num_parties=2, min_live=1)
    c.mark_dead(0)
    with pytest.raises(RuntimeError, match="min_live"):
        c.mark_dead(1)
    # the failed transition must not have corrupted the published epoch
    assert c.epoch.live_mask == (False, True)
    with pytest.raises(ValueError):
        c.mark_dead(7)  # out of range


def test_controller_consumes_heartbeats():
    mon = HeartbeatMonitor(timeout_s=0.15)
    c = PartyLivenessController(num_parties=2, monitor=mon)
    c.bind_party(0, 100)
    c.bind_party(1, 101)
    assert c.poll().all_live
    time.sleep(0.25)
    mon.heartbeat(100)  # party 0 keeps beating; party 1 goes silent
    ep = c.poll()
    assert ep.live_mask == (True, False) and ep.version == 1
    # the node comes back: its next heartbeat re-admits the party
    mon.heartbeat(101)
    ep = c.poll()
    assert ep.all_live and ep.version == 2


def test_controller_consumes_external_dead_list():
    """The scheduler-roster consumer path: poll() accepts the dead list a
    SchedulerClient.dead_nodes() call returned."""
    c = PartyLivenessController(num_parties=2)
    c.bind_party(0, 9)
    c.bind_party(1, 11)
    ep = c.poll(dead_nodes=[11])
    assert ep.live_mask == (True, False)
    assert c.poll(dead_nodes=[]).all_live


# --------------------------------------------------------------------------
# chaos schedules: determinism and the engine
# --------------------------------------------------------------------------

def test_chaos_spec_roundtrip_and_validation():
    s = ChaosSchedule.from_spec(
        "seed=7;blackout@3:party=1,steps=4;drop@10:rate=30,steps=5")
    assert s.seed == 7
    assert ChaosEvent(3, "blackout", party=1) in s.events
    assert ChaosEvent(7, "readmit", party=1) in s.events
    assert ChaosEvent(10, "drop_rate", rate=30) in s.events
    assert ChaosEvent(15, "drop_clear") in s.events
    # canonical spec round-trips to the same schedule
    s2 = ChaosSchedule.from_spec(s.spec())
    assert s2.events == s.events and s2.seed == s.seed
    # flap = 1-step blackout by default
    f = ChaosSchedule.from_spec("flap@5:party=0")
    assert ChaosEvent(5, "blackout", party=0) in f.events
    assert ChaosEvent(6, "readmit", party=0) in f.events
    with pytest.raises(ValueError, match="unknown chaos kind"):
        ChaosSchedule.from_spec("explode@3:party=1")
    with pytest.raises(ValueError, match="does not take"):
        ChaosSchedule.from_spec("blackout@3:rate=30")
    with pytest.raises(ValueError, match="not in"):
        ChaosSchedule.from_spec("drop@3:rate=130")


def test_chaos_random_is_deterministic_per_seed():
    a = ChaosSchedule.random(seed=42, steps=50, num_parties=4,
                             blackouts=2, drop_epochs=1)
    b = ChaosSchedule.random(seed=42, steps=50, num_parties=4,
                             blackouts=2, drop_epochs=1)
    c = ChaosSchedule.random(seed=43, steps=50, num_parties=4,
                             blackouts=2, drop_epochs=1)
    assert a.events == b.events
    assert a.events != c.events
    # keep_party never blacks out
    assert all(e.party != 0 for e in a.events
               if e.kind == "blackout")


def test_chaos_engine_drives_controller_and_drop_hook():
    from geomx_tpu.service import protocol

    ctrl = PartyLivenessController(num_parties=2)
    sched = ChaosSchedule.from_spec(
        "seed=5;blackout@2:party=1,steps=2;drop@6:rate=40,steps=2")
    with ChaosEngine(sched, ctrl) as eng:
        assert eng.tick(0) == []
        fired = eng.tick(2)
        assert [e.kind for e in fired] == ["blackout"]
        assert ctrl.epoch.live_mask == (True, False)
        # skipped steps still apply their events (epoch-grained callers)
        fired = eng.tick(7)
        kinds = [e.kind for e in fired]
        assert kinds == ["readmit", "drop_rate"]
        assert ctrl.epoch.all_live
        assert protocol.drop_rate() == 40
        eng.tick(8)
        assert protocol.drop_rate() == 0
        # replays are idempotent: a second tick of the same step is a no-op
        assert eng.tick(8) == []
    assert protocol.drop_rate() == 0


def test_drop_rate_override_wins_over_env(monkeypatch):
    from geomx_tpu.service import protocol
    monkeypatch.setenv("GEOMX_DROP_MSG", "15")
    assert protocol.drop_rate() == 15
    protocol.set_drop_rate_override(80)
    try:
        assert protocol.drop_rate() == 80
    finally:
        protocol.set_drop_rate_override(None)
    assert protocol.drop_rate() == 15


# --------------------------------------------------------------------------
# degraded-mode numerics
# --------------------------------------------------------------------------

def test_renormalized_mean_bit_exact_over_survivors():
    """The load-bearing numeric claim: inside ONE program, the masked
    dc-tier aggregate equals the mean over survivors bit for bit — the
    dead party's shard is multiplied to exact zeros before the psum, and
    adding exact zeros is exact in IEEE float."""
    topo = HiPSTopology(num_parties=3, workers_per_party=1)
    mesh = topo.build_mesh()
    fsa = FSA(bucket_bytes=0).bind_topology(topo)
    fsa.bind_membership((True, True, False))
    assert fsa.num_live == 2

    rng = np.random.RandomState(0)
    g = {"w": rng.randn(3, 1, 257).astype(np.float32),
         "b": rng.randn(3, 1, 5).astype(np.float32)}
    state = fsa.init_state(jax.tree.map(lambda a: a[0, 0], g))

    def f(gs):
        gl = jax.tree.map(lambda a: a[0, 0], gs)
        out, _ = fsa.sync_grads(gl, gl, state, jnp.zeros((), jnp.int32))
        return jax.tree.map(lambda a: a[None, None], out)

    fn = shard_map_compat(f, mesh, in_specs=(P("dc", "worker"),),
                          out_specs=P("dc", "worker"))
    out = jax.device_get(jax.jit(fn)(g))
    for k in g:
        expect = (g[k][0, 0] + g[k][1, 0]) / np.float32(2.0)
        for p in range(3):  # every replica (including the dead party's
            # device, which still executes the SPMD program) holds the
            # survivor mean exactly
            assert np.array_equal(out[k][p, 0], expect), (k, p)


def test_mixed_sync_degraded_mean_bit_exact():
    topo = HiPSTopology(num_parties=2, workers_per_party=1)
    mesh = topo.build_mesh()
    ms = MixedSync(bucket_bytes=0).bind_topology(topo)
    ms.bind_membership((False, True))

    rng = np.random.RandomState(1)
    g = {"w": rng.randn(2, 1, 33).astype(np.float32)}
    params = jax.tree.map(lambda a: a[0, 0], g)
    state = ms.init_state(params)

    def f(gs, ss):
        gl = jax.tree.map(lambda a: a[0, 0], gs)
        sl = jax.tree.map(lambda a: a[0, 0], ss)
        out, _ = ms.sync_grads(gl, params, sl, jnp.zeros((), jnp.int32))
        return jax.tree.map(lambda a: a[None, None], out)

    stack = jax.tree.map(lambda a: np.broadcast_to(a[None, None],
                                                   (2, 1) + a.shape).copy(),
                         state)
    fn = shard_map_compat(f, mesh, in_specs=(P("dc", "worker"),
                                             P("dc", "worker")),
                          out_specs=P("dc", "worker"))
    out = jax.device_get(jax.jit(fn)(g, stack))
    # sole survivor is party 1: the aggregate is its gradient, exactly
    assert np.array_equal(out["w"][0, 0], g["w"][1, 0])
    assert np.array_equal(out["w"][1, 0], g["w"][1, 0])


def _mk_trainer(sync, parties=2, workers=1, lr=0.05, model=None):
    topo = HiPSTopology(num_parties=parties, workers_per_party=workers)
    trainer = Trainer(model or GeoCNN(num_classes=10), topo,
                      optax.sgd(lr), sync=sync, donate=False)
    rng = np.random.RandomState(0)
    b = 8
    x = (rng.rand(parties, workers, b, 32, 32, 3) * 255).astype(np.uint8)
    y = rng.randint(0, 10, size=(parties, workers, b)).astype(np.int32)
    sh = topo.batch_sharding(trainer.mesh)
    state = trainer.init_state(jax.random.PRNGKey(0), x[0, 0, :2])
    return trainer, state, jax.device_put(x, sh), jax.device_put(y, sh), x, y


def test_degraded_trainer_step_matches_survivor_only_run():
    """End-to-end: a degraded 2-party step (party 1 dead) equals a
    1-party run of the survivor from the same state, and the step
    metadata reports the static live count."""
    trainer, state, xb, yb, x, y = _mk_trainer(FSA())
    s_full, m_full = trainer.train_step(state, xb, yb)
    assert float(m_full["num_live_parties"]) == 2.0

    state_deg = trainer.apply_membership(state, (True, False))
    s_deg, m_deg = trainer.train_step(state_deg, xb, yb)
    assert float(m_deg["num_live_parties"]) == 1.0

    topo1 = HiPSTopology(1, 1)
    solo = Trainer(GeoCNN(num_classes=10), topo1, optax.sgd(0.05),
                   sync=FSA(), donate=False)
    sh1 = topo1.batch_sharding(solo.mesh)
    st1 = solo.init_state(jax.random.PRNGKey(0), x[0, 0, :2])
    s_solo, m_solo = solo.train_step(st1, jax.device_put(x[:1], sh1),
                                     jax.device_put(y[:1], sh1))
    # same seed -> same init; the degraded aggregate IS the survivor's
    # gradient (ulp tolerance: the 2-device and 1-device programs may
    # compile reductions in different association orders)
    for a, b in zip(jax.tree.leaves(unreplicate_tree(s_deg.params)),
                    jax.tree.leaves(unreplicate_tree(s_solo.params))):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-8)
    # degraded metrics are the survivor's, not a half-dead average
    np.testing.assert_allclose(float(m_deg["loss"]), float(m_solo["loss"]),
                               rtol=1e-6)


def test_apply_membership_recompile_boundary_caches_programs():
    trainer, state, xb, yb, _, _ = _mk_trainer(FSA())
    full_step = trainer.train_step
    state = trainer.apply_membership(state, (True, False))
    deg_step = trainer.train_step
    assert deg_step is not full_step
    # no-op rebind: same mask, same program, same state object
    assert trainer.apply_membership(state, (True, False)) is state
    # re-admission reuses the cached all-live program
    state = trainer.apply_membership(state, (True, True))
    assert trainer.train_step is full_step
    # ...and the degraded program is cached too
    state = trainer.apply_membership(state, [True, False])
    assert trainer.train_step is deg_step


def test_hfa_rejects_degraded_mask():
    topo = HiPSTopology(num_parties=2, workers_per_party=1)
    hfa = HFA(k1=2, k2=2).bind_topology(topo)
    with pytest.raises(ValueError, match="does not support"):
        hfa.bind_membership((True, False))
    # the all-live mask is always acceptable (clears degraded mode)
    hfa.bind_membership((True, True))
    assert hfa.live_parties is None


def test_multigps_trainer_rejects_membership():
    trainer, state, _, _, _, _ = _mk_trainer(FSA())
    trainer._mgps = object()  # stand-in: a MultiGPS-enabled trainer
    with pytest.raises(ValueError, match="MULTI_GPS"):
        trainer.apply_membership(state, (True, False))


def test_mask_validation():
    with pytest.raises(ValueError, match="at least one live"):
        normalize_live_mask((False, False), 2)
    with pytest.raises(ValueError, match="entries"):
        normalize_live_mask((True,), 2)


# --------------------------------------------------------------------------
# residual / buffer policy across a blackout / re-admit cycle
# --------------------------------------------------------------------------

def _dc_float_leaves(state):
    return [leaf for leaf in jax.tree.leaves(
        unreplicate_tree(state.sync_state)["dc_comp"])
        if hasattr(leaf, "dtype") and np.issubdtype(leaf.dtype,
                                                    np.floating)]


def test_residual_policy_reset_and_carry():
    """BSC error-feedback residuals across a membership change: "reset"
    zeroes them (the documented default), "carry" preserves them
    bit-exactly."""
    from geomx_tpu.compression import get_compressor
    trainer, state, xb, yb, _, _ = _mk_trainer(
        FSA(dc_compressor=get_compressor("bsc,0.25")))
    for _ in range(2):
        state, _ = trainer.train_step(state, xb, yb)
    pre = _dc_float_leaves(state)
    assert any(np.any(leaf != 0) for leaf in pre), "no residuals accumulated"

    s_carry = trainer.apply_membership(state, (True, False),
                                       policy="carry")
    for a, b in zip(pre, _dc_float_leaves(s_carry)):
        assert np.array_equal(a, b)

    # back to full membership (cached program), then a reset blackout
    s_carry = trainer.apply_membership(s_carry, (True, True),
                                       policy="carry")
    s_reset = trainer.apply_membership(s_carry, (True, False),
                                       policy="reset")
    assert all(not np.any(leaf) for leaf in _dc_float_leaves(s_reset)), \
        "reset policy left residuals behind"
    # the degraded program still runs from the reset state
    s2, m = trainer.train_step(s_reset, xb, yb)
    assert np.isfinite(float(m["loss"]))
    with pytest.raises(ValueError, match="unknown residual policy"):
        trainer.apply_membership(s2, (True, True), policy="discard")


def test_pipelined_drain_under_mid_flight_party_loss():
    """A party dies with an aggregate in flight: the reset policy
    discards the in-flight buffer (launched under the old membership),
    so the subsequent drain applies a zero aggregate — params unchanged,
    no NaNs, and the run can keep training degraded."""
    trainer, state, xb, yb, _, _ = _mk_trainer(PipelinedSync(FSA()))
    for _ in range(2):
        state, _ = trainer.train_step(state, xb, yb)
    infl = unreplicate_tree(state.sync_state)["inner"]["dc_comp"]["inflight"]
    assert any(np.any(b != 0) for b in infl), "pipeline never filled"

    state = trainer.apply_membership(state, (True, False), policy="reset")
    infl = unreplicate_tree(state.sync_state)["inner"]["dc_comp"]["inflight"]
    assert all(not np.any(b) for b in infl), \
        "reset policy kept the mixed-membership in-flight aggregate"

    p_before = unreplicate_tree(state.params)
    drained = trainer.drain_pipeline(state)
    p_after = unreplicate_tree(drained.params)
    for a, b in zip(jax.tree.leaves(p_before), jax.tree.leaves(p_after)):
        assert np.array_equal(a, b)
    # degraded pipelined training continues (warmup bubble refills)
    s2, m = trainer.train_step(drained, xb, yb)
    assert np.isfinite(float(m["loss"]))
    assert float(m["num_live_parties"]) == 1.0


def test_pipelined_carry_policy_drains_renormalized_aggregate():
    """The documented alternative: "carry" keeps the in-flight aggregate
    across the change; the drain applies it (renormalized over the NEW
    survivor count) — params move, stay finite."""
    trainer, state, xb, yb, _, _ = _mk_trainer(PipelinedSync(FSA()))
    for _ in range(2):
        state, _ = trainer.train_step(state, xb, yb)
    state = trainer.apply_membership(state, (True, False), policy="carry")
    p_before = unreplicate_tree(state.params)
    drained = trainer.drain_pipeline(state)
    p_after = unreplicate_tree(drained.params)
    moved = any(not np.array_equal(a, b) for a, b in
                zip(jax.tree.leaves(p_before), jax.tree.leaves(p_after)))
    assert moved, "carry policy drained a zero aggregate"
    assert all(np.all(np.isfinite(leaf)) for leaf in jax.tree.leaves(p_after))


# --------------------------------------------------------------------------
# re-admission catch-up
# --------------------------------------------------------------------------

def test_catchup_payload_roundtrip():
    """The catch-up blob a returning party installs restores the FULL
    state (params, optimizer, model AND sync state) bit-exactly, in the
    checkpoint tree format."""
    trainer, state, xb, yb, _, _ = _mk_trainer(FSA())
    state, _ = trainer.train_step(state, xb, yb)
    blob = trainer.catchup_payload(state)
    assert isinstance(blob, bytes) and len(blob) > 1000
    restored = trainer.admit_party(blob)
    for a, b in zip(jax.tree.leaves(jax.device_get(state)),
                    jax.tree.leaves(jax.device_get(restored))):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # the admitted state is trainable (shardings landed correctly)
    s2, m = trainer.train_step(restored, xb, yb)
    assert np.isfinite(float(m["loss"]))


def test_pack_catchup_matches_checkpoint_format(tmp_path):
    """Catch-up and checkpoint share ONE serialization: the blob a
    returning party installs is byte-identical to a checkpoint of the
    same tree, so restore-from-disk and catch-up-from-peer can never
    diverge in what they accept."""
    from geomx_tpu.utils.checkpoint import save_checkpoint
    tree = {"a": np.arange(5, dtype=np.float32), "b": {"c": np.ones(3)}}
    blob = pack_catchup(tree)
    back = unpack_catchup(blob)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        assert np.array_equal(a, b)
    path = save_checkpoint(str(tmp_path / "st"), tree)
    with open(path, "rb") as f:
        assert blob == f.read()


# --------------------------------------------------------------------------
# service plane: roster epochs, eviction
# --------------------------------------------------------------------------

def test_scheduler_roster_epochs_and_evict():
    from geomx_tpu.service.scheduler import GeoScheduler, SchedulerClient
    sched = GeoScheduler().start()
    try:
        c0 = SchedulerClient(("127.0.0.1", sched.port))
        c0.register("worker", port=0, tag="0.0")
        e0 = c0.roster_epoch
        assert e0 >= 1
        c1 = SchedulerClient(("127.0.0.1", sched.port))
        c1.register("worker", port=0, tag="0.1")
        assert c1.roster_epoch == e0 + 1
        # eviction: roster shrinks, epoch bumps
        r = c0.evict(c1.node_id)
        assert r["evicted"] and r["epoch"] == e0 + 2
        roster = c0.cluster()
        assert all(e[0] != c1.node_id for e in roster.get("worker", []))
        # evicting an unknown node changes nothing
        r = c0.evict(9999)
        assert not r["evicted"] and r["epoch"] == e0 + 2
        c0.close()
        c1.close()
    finally:
        sched.stop()


def test_server_side_worker_eviction_unstalls_sync_round():
    """2-worker sync gate, one worker dies after the other pushed: the
    eviction closes the round at the reduced count instead of stalling
    the pull forever, and later rounds complete at the new gate."""
    from geomx_tpu.service import GeoPSClient, GeoPSServer
    server = GeoPSServer(num_workers=2, mode="sync", accumulate=True).start()
    try:
        c0 = GeoPSClient(("127.0.0.1", server.port), sender_id=0)
        c0.init("w", np.zeros(16, np.float32))
        c0.push("w", np.ones(16, np.float32))  # round 0: 1/2 merged
        # worker 1 never arrives; evict it server-side
        assert c0.evict_worker(1) == 1
        out = c0.pull("w")  # completes: the round closed at count 1
        np.testing.assert_allclose(out, np.ones(16))
        # the next round needs only the survivor
        c0.push("w", np.full(16, 2.0, np.float32))
        np.testing.assert_allclose(c0.pull("w"), np.full(16, 3.0))
        # the gate never shrinks to zero
        with pytest.raises(Exception, match="evict"):
            c0.evict_worker(0)
        c0.stop_server()
        c0.close()
    finally:
        server.stop()


def test_eviction_of_mid_round_pusher_still_waits_for_all_survivors():
    """A worker that PUSHED into the open round and then died: its merge
    stands but must stop counting toward the gate — otherwise the round
    closes one survivor early and every later round permanently
    interleaves survivors' steps.  Also: double-eviction is rejected."""
    from geomx_tpu.service import GeoPSClient, GeoPSServer
    server = GeoPSServer(num_workers=3, mode="sync", accumulate=True).start()
    try:
        cs = [GeoPSClient(("127.0.0.1", server.port), sender_id=i)
              for i in range(3)]
        cs[0].init("w", np.zeros(8, np.float32))
        cs[0].push("w", np.full(8, 1.0, np.float32))  # A contributes...
        assert cs[1].evict_worker(0) == 2             # ...then dies
        # the round must NOT close yet: both survivors still owe a push
        cs[1].push("w", np.full(8, 2.0, np.float32))
        cs[2].push("w", np.full(8, 4.0, np.float32))
        # A's merged contribution stands: 1 + 2 + 4
        np.testing.assert_allclose(cs[1].pull("w"), np.full(8, 7.0))
        # the next round closes with exactly the two survivors
        cs[1].push("w", np.full(8, 10.0, np.float32))
        cs[2].push("w", np.full(8, 20.0, np.float32))
        np.testing.assert_allclose(cs[1].pull("w"), np.full(8, 37.0))
        # a second liveness agent reacting to the same death must not
        # shrink the gate again
        with pytest.raises(Exception, match="already evicted"):
            cs[2].evict_worker(0)
        cs[1].stop_server()
        for c in cs:
            c.close()
    finally:
        server.stop()


# --------------------------------------------------------------------------
# config surface
# --------------------------------------------------------------------------

def test_resilience_env_knobs(monkeypatch):
    from geomx_tpu.config import GeoConfig
    monkeypatch.setenv("GEOMX_RESILIENCE_RESIDUALS", "carry")
    monkeypatch.setenv("GEOMX_RESILIENCE_MIN_LIVE", "2")
    monkeypatch.setenv("GEOMX_CHAOS_SCHEDULE",
                       "seed=9;blackout@2:party=1,steps=2")
    cfg = GeoConfig.from_env(num_parties=3)
    assert cfg.resilience_residuals == "carry"
    assert cfg.resilience_min_live == 2
    sched = ChaosSchedule.from_config(cfg)
    assert sched.seed == 9 and sched.last_step == 4
    # the controller consumes the config floor: with min_live=2 of 3
    # parties, a second death raises instead of degrading further
    ctrl = PartyLivenessController.from_config(cfg)
    assert ctrl.min_live == 2 and ctrl.num_parties == 3
    ctrl.mark_dead(2)
    with pytest.raises(RuntimeError, match="min_live"):
        ctrl.mark_dead(1)
    # no chaos configured -> no schedule
    assert ChaosSchedule.from_config(GeoConfig()) is None


def test_blackout_readmit_cycle_leaves_replicas_identical():
    """A seeded schedule blacks party 1 out at step 3 for three steps:
    the run steps through both recompile boundaries, the live count goes
    2 -> 1 -> 2, the survivors have a catch-up payload for the returning
    party, and after the cycle every replica holds the same params."""
    trainer, state, xb, yb, _, _ = _mk_trainer(FSA())
    ctrl = PartyLivenessController(num_parties=2)
    sched = ChaosSchedule.from_spec("seed=1234;blackout@3:party=1,steps=3")
    current, live, catchup = ctrl.epoch, [], None
    with ChaosEngine(sched, ctrl) as eng:
        for step in range(9):
            eng.tick(step)
            ep = ctrl.epoch
            if ep.version != current.version:
                if ep.num_live > current.num_live:
                    catchup = trainer.catchup_payload(state)
                state = trainer.apply_membership(state, ep)
                current = ep
            state, m = trainer.train_step(state, xb, yb)
            live.append(float(m["num_live_parties"]))
    assert live == [2.0] * 3 + [1.0] * 3 + [2.0] * 3
    assert catchup
    for leaf in jax.tree.leaves(jax.device_get(state.params)):
        assert np.array_equal(leaf[0, 0], leaf[1, 0])
