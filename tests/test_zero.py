"""ZeRO-sharded bucketed weight update (train/zero.py, GEOMX_ZERO).

Evidence layers, all on the 8-virtual-device CPU mesh:

- *Numeric identity*: the sharded update (psum_scatter -> shard-local
  optimizer -> all_gather) lands on the replicated FSA trajectory
  bit-for-close for vanilla SGD+momentum and Adam, composed with the
  pipelined engine (drain included), degraded membership, and MixedSync
  (incl. DCASGD shard-wise compensation).
- *Memory*: per-chip optimizer + dc-tier EF state bytes shrink ~1/W.
- *Structure*: the DCE'd weight path carries psum_scatter + all_gather
  over the worker axis and NO worker-axis psum; the donated sharded
  TrainState is fully covered by input_output_aliases; the compressed
  shard path passes the GX-PURITY audit at the shard-dense floor.
- *Checkpointing*: save/restore is bit-exact mid-pipeline on the same
  topology, re-shards onto a different worker count, and a GEOMX_ZERO
  mismatch is rejected with a clear error; the catch-up payload
  round-trips per-worker shards.
- *Rejections*: HFA, MultiGPS, bucketing-off and pipelined DCASGD all
  fail loudly instead of silently running a replicated update.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from geomx_tpu.config import GeoConfig
from geomx_tpu.models import get_model
from geomx_tpu.sync import get_sync_algorithm
from geomx_tpu.topology import HiPSTopology
from geomx_tpu.train import Trainer

P_, W_ = 2, 4
STEPS = 3


def _data(steps=STEPS, nw=W_, seed=0, same_per_worker=False):
    rng = np.random.RandomState(seed)
    if same_per_worker:
        # identical per-worker batches: the hierarchical mean is then
        # invariant to the worker count (cross-topology reshard tests)
        x1 = (rng.rand(steps, P_, 1, 2, 8, 8, 3) * 255).astype(np.uint8)
        y1 = rng.randint(0, 10, size=(steps, P_, 1, 2)).astype(np.int32)
        x = np.broadcast_to(x1, (steps, P_, nw, 2, 8, 8, 3)).copy()
        y = np.broadcast_to(y1, (steps, P_, nw, 2)).copy()
        return x, y
    x = (rng.rand(steps, P_, nw, 2, 8, 8, 3) * 255).astype(np.uint8)
    y = rng.randint(0, 10, size=(steps, P_, nw, 2)).astype(np.int32)
    return x, y


def _trainer(zero, nw=W_, tx=None, **over):
    topo = HiPSTopology(num_parties=P_, workers_per_party=nw)
    cfg = GeoConfig(num_parties=P_, workers_per_party=nw, zero=zero,
                    **over)
    tr = Trainer(get_model("mlp", num_classes=10), topo,
                 tx or optax.sgd(0.1, momentum=0.9),
                 sync=get_sync_algorithm(cfg), config=cfg)
    return tr, topo


def _run(tr, topo, st, xs, ys, drain=False):
    sh = topo.batch_sharding(tr.mesh)
    for s in range(len(xs)):
        st, _m = tr.train_step(st, jax.device_put(xs[s], sh),
                               jax.device_put(ys[s], sh))
    if drain:
        st = tr.drain_pipeline(st)
    jax.block_until_ready(st.step)
    return st


def _params00(st):
    return jax.tree.map(lambda a: np.asarray(a, np.float64)[0, 0],
                        st.params)


def _gap(a, b):
    return max(jax.tree.leaves(jax.tree.map(
        lambda u, v: float(np.max(np.abs(u - v))), a, b)))


# --------------------------------------------------------------------------
# numeric identity vs the replicated update
# --------------------------------------------------------------------------

@pytest.mark.parametrize("tx_fn", [
    lambda: optax.sgd(0.1, momentum=0.9),
    lambda: optax.adam(1e-3),
], ids=["sgd_momentum", "adam"])
def test_zero_matches_replicated(tx_fn):
    xs, ys = _data()
    ps = []
    for zero in (False, True):
        tr, topo = _trainer(zero, tx=tx_fn())
        st = tr.init_state(jax.random.PRNGKey(0), xs[0, 0, 0])
        ps.append(_params00(_run(tr, topo, st, xs, ys)))
    assert _gap(*ps) <= 1e-6


def test_zero_pipelined_matches_replicated_pipelined():
    xs, ys = _data()
    ps = []
    for zero in (False, True):
        tr, topo = _trainer(zero, pipeline_depth=1)
        st = tr.init_state(jax.random.PRNGKey(0), xs[0, 0, 0])
        ps.append(_params00(_run(tr, topo, st, xs, ys, drain=True)))
    assert _gap(*ps) <= 1e-6


def test_zero_degraded_membership_matches_replicated():
    xs, ys = _data()
    ps = []
    for zero in (False, True):
        tr, topo = _trainer(zero)
        st = tr.init_state(jax.random.PRNGKey(0), xs[0, 0, 0])
        st = tr.apply_membership(st, (True, False))
        ps.append(_params00(_run(tr, topo, st, xs, ys)))
    assert _gap(*ps) <= 1e-6


def test_zero_mixed_sync_with_dcasgd_matches_replicated():
    xs, ys = _data()
    ps = []
    for zero in (False, True):
        tr, topo = _trainer(zero, sync_mode="mixed",
                            mixed_pull_interval=2, dcasgd=True)
        st = tr.init_state(jax.random.PRNGKey(0), xs[0, 0, 0])
        ps.append(_params00(_run(tr, topo, st, xs, ys)))
    assert _gap(*ps) <= 1e-6


def test_zero_membership_carry_keeps_worker_shards():
    """The carry residual policy must not round-trip sharded dc state
    through a (0, 0) copy — worker slots would all inherit worker 0's
    EF residuals.  bsc accumulates distinct per-shard residuals; after
    a carry membership change the run must still match a replicated
    carry run step for step is too strong (selection granularity
    differs), so assert the shard state itself survives untouched."""
    xs, ys = _data()
    tr, topo = _trainer(True, compression="bsc,0.05,min_sparse_size=16")
    st = tr.init_state(jax.random.PRNGKey(0), xs[0, 0, 0])
    st = _run(tr, topo, st, xs, ys)
    # the compressed shard path trains: finite params after its steps
    assert all(np.isfinite(np.asarray(leaf)).all()
               for leaf in jax.tree.leaves(st.params))
    before = jax.tree.map(np.asarray, st.sync_state)
    st2 = tr.apply_membership(st, (True, False), policy="carry")
    after = jax.tree.map(np.asarray, st2.sync_state)
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        np.testing.assert_array_equal(a, b)
    # and the degraded program still runs on the carried shards
    st2 = _run(tr, topo, st2, xs[:1], ys[:1])
    assert int(st2.step) == STEPS + 1


# --------------------------------------------------------------------------
# memory: per-chip state shrinks ~1/W
# --------------------------------------------------------------------------

def test_zero_per_chip_state_bytes_shrink():
    xs, _ = _data()
    sizes = {}
    for zero in (False, True):
        tr, topo = _trainer(zero, tx=optax.adam(1e-3))
        st = tr.init_state(jax.random.PRNGKey(0), xs[0, 0, 0])
        n_dev = P_ * W_
        sizes[zero] = sum(
            leaf.size * leaf.dtype.itemsize
            for leaf in jax.tree.leaves(st.opt_state)) / n_dev
    ratio = sizes[True] / sizes[False]
    # Adam: mu+nu shard-shaped; padding + count scalars keep it a hair
    # above exactly 1/W
    assert ratio < 1.5 / W_, (sizes, ratio)


def test_zero_ef_residuals_are_shard_local():
    xs, _ = _data()
    tr, topo = _trainer(True, compression="bsc,0.05,min_sparse_size=16")
    st = tr.init_state(jax.random.PRNGKey(0), xs[0, 0, 0])
    dc = st.sync_state["dc_comp"]
    bucketed = tr.sync.dc_compressor
    params0 = jax.tree.map(lambda a: a[0, 0], st.params)
    bk = bucketed.zero_bucketer(jax.tree.leaves(params0))
    for leaf in jax.tree.leaves(dc):
        # every EF leaf is [P, W, shard]: 1/W of its padded bucket
        assert leaf.shape[2] in {n // W_ for n in bk.bucket_sizes}, \
            leaf.shape


# --------------------------------------------------------------------------
# structure: collectives, donation, purity
# --------------------------------------------------------------------------

@pytest.mark.parametrize("compression", ["none", "bsc,0.01"])
def test_zero_weight_path_swaps_allreduce_for_scatter_gather(compression):
    """On the path to params / opt_state (BatchNorm-stat pmeans feed
    model_state and are left out on purpose: statistics maintenance, not
    the weight update) the replicated step keeps its worker-axis psum;
    the ZeRO step keeps psum_scatter + all_gather and no worker-axis
    psum."""
    from geomx_tpu.analysis.passes import (_GATHER_PRIMS, _SCATTER_PRIMS,
                                           weight_path_collectives)
    xs, ys = _data()
    counts = {}
    for zero in (False, True):
        tr, topo = _trainer(zero, compression=compression)
        st = tr.init_state(jax.random.PRNGKey(0), xs[0, 0, 0])
        sh = topo.batch_sharding(tr.mesh)
        on_path, _whole = weight_path_collectives(
            tr.train_step, st, jax.device_put(xs[0], sh),
            jax.device_put(ys[0], sh))
        counts[zero] = on_path.get("worker", {})
    rep_w, zero_w = counts[False], counts[True]
    assert rep_w.get("psum", 0) > 0
    assert not any(k in rep_w for k in _SCATTER_PRIMS)
    assert zero_w.get("psum", 0) == 0, zero_w
    assert sum(zero_w.get(k, 0) for k in _SCATTER_PRIMS) >= 1
    assert sum(zero_w.get(k, 0) for k in _GATHER_PRIMS) >= 1


def test_zero_donated_step_aliases_sharded_state():
    """Donation coverage of the sharded TrainState: the compiled
    input_output_alias table must cover every donated state buffer —
    including the shard-shaped optimizer and EF-residual leaves."""
    from geomx_tpu.analysis import AuditContext, DonationPass
    from geomx_tpu.analysis.passes import parse_compiled_aliases

    topo = HiPSTopology(num_parties=P_, workers_per_party=W_)
    cfg = GeoConfig(num_parties=P_, workers_per_party=W_, zero=True,
                    compression="bsc,0.05,min_sparse_size=16")
    tr = Trainer(get_model("mlp", num_classes=10), topo,
                 optax.sgd(0.1, momentum=0.9),
                 sync=get_sync_algorithm(cfg), config=cfg, donate=True)
    xs, ys = _data()
    st = tr.init_state(jax.random.PRNGKey(0), xs[0, 0, 0])
    sh = topo.batch_sharding(tr.mesh)
    xb, yb = jax.device_put(xs[0], sh), jax.device_put(ys[0], sh)
    lowered = tr.train_step.lower(st, xb, yb)
    compiled_params = parse_compiled_aliases(lowered.compile().as_text())
    n_state = len(jax.tree.leaves(st))
    expect = [(tuple(leaf.shape), str(leaf.dtype))
              for leaf in jax.tree.leaves((st.opt_state,
                                           st.sync_state["dc_comp"]))]
    assert expect
    ctx = AuditContext(lowered_text=lowered.as_text(), extras={
        "donated_positions": list(range(n_state)),
        "compiled_alias_params": compiled_params,
        "expect_aliased": expect})
    findings = DonationPass().run(None, ctx)
    assert findings == [], [f.format() for f in findings]
    assert compiled_params == frozenset(range(n_state))


@pytest.mark.parametrize("sparse_agg", [False, True],
                         ids=["gather", "sparse_agg"])
def test_zero_compressed_shard_path_purity(sparse_agg):
    """GX-PURITY at the shard floor: the ZeRO dc tier's collectives all
    carry sub-shard payloads for bsc, on the gather path and on the
    owner-routed merge; a decompress-before-collective variant is
    flagged."""
    from geomx_tpu.analysis import audit_zero_compressed_path
    from geomx_tpu.compression.bisparse import BiSparseCompressor
    from geomx_tpu.compression.bucketing import BucketedCompressor
    from geomx_tpu.train.zero import ZeroPlan

    params = {"a": jnp.zeros((6000,), jnp.float32),
              "b": jnp.zeros((300,), jnp.float32)}
    comp = BucketedCompressor(BiSparseCompressor(
        ratio=0.05, min_sparse_size=16, sparse_agg=sparse_agg))
    ZeroPlan(W_).bind_compressor(comp)
    assert audit_zero_compressed_path(comp, params, num_shards=W_) == []

    class DenseLeak(BiSparseCompressor):
        def allreduce_leaf(self, g, state, axis_name, axis_size):
            from jax import lax
            u, v = state
            vals, idx, u, v = self.compress(
                g.reshape(-1).astype(jnp.float32), u.reshape(-1),
                v.reshape(-1))
            dense = self.decompress(vals, idx, g.size)
            out = lax.psum(dense, axis_name)  # dense shard on the wire
            return (out.reshape(g.shape).astype(g.dtype),
                    (u.reshape(g.shape), v.reshape(g.shape)))

    leaky = BucketedCompressor(DenseLeak(
        ratio=0.05, min_sparse_size=16, sparse_agg=sparse_agg))
    ZeroPlan(W_).bind_compressor(leaky)
    findings = audit_zero_compressed_path(leaky, params, num_shards=W_)
    assert findings and all(f.rule_id == "GX-PURITY-001"
                            for f in findings)


def test_zero_membership_recompile_keeps_collective_signature_auditable():
    """The Trainer's GX-COLLECTIVE-002 boundary must work unchanged for
    ZeRO programs: a membership mask changes constants, never the
    scatter/gather sequence."""
    xs, ys = _data()
    tr, topo = _trainer(True, audit=True)
    st = tr.init_state(jax.random.PRNGKey(0), xs[0, 0, 0])
    sh = topo.batch_sharding(tr.mesh)
    state = st
    state, _ = tr.fit(state, tr.make_loader(
        xs.reshape(-1, 8, 8, 3), ys.reshape(-1), batch_size=2),
        epochs=1)
    # the degraded program's signature must diff clean against the armed
    # full-membership reference (no AuditError)
    state = tr.apply_membership(state, (True, False))
    assert tr._membership == (True, False)


# --------------------------------------------------------------------------
# checkpoint / catch-up
# --------------------------------------------------------------------------

def _mid_pipeline_run(nw, xs, ys, upto):
    tr, topo = _trainer(True, nw=nw, pipeline_depth=1)
    st = tr.init_state(jax.random.PRNGKey(0), xs[0, 0, 0])
    st = _run(tr, topo, st, xs[:upto], ys[:upto])
    return tr, topo, st


def test_zero_checkpoint_same_topology_bit_exact(tmp_path):
    xs, ys = _data(steps=6)
    tr, topo, st = _mid_pipeline_run(W_, xs, ys, upto=3)
    path = tr.save_checkpoint(str(tmp_path / "mid"), st)
    full = _params00(_run(tr, topo, st, xs[3:], ys[3:], drain=True))
    tr2, topo2 = _trainer(True, pipeline_depth=1)
    template = tr2.init_state(jax.random.PRNGKey(0), xs[0, 0, 0])
    st2 = tr2.load_checkpoint(path, template)
    resumed = _params00(_run(tr2, topo2, st2, xs[3:], ys[3:], drain=True))
    assert _gap(full, resumed) == 0.0


def test_zero_checkpoint_reshards_2x4_to_2x2(tmp_path):
    """Save mid-pipeline on 2x4, restore onto 2x2 (reshard on load) and
    resume: with identical per-worker batches the two-tier mean is
    worker-count invariant, so the resumed trajectory is bit-exact."""
    xs4, ys4 = _data(steps=6, nw=4, same_per_worker=True)
    xs2 = xs4[:, :, :2].copy()
    ys2 = ys4[:, :, :2].copy()
    tr4, topo4, st = _mid_pipeline_run(4, xs4, ys4, upto=3)
    path = tr4.save_checkpoint(str(tmp_path / "mid"), st)
    full = _params00(_run(tr4, topo4, st, xs4[3:], ys4[3:], drain=True))

    tr2, topo2 = _trainer(True, nw=2, pipeline_depth=1)
    template = tr2.init_state(jax.random.PRNGKey(0), xs2[0, 0, 0])
    st2 = tr2.load_checkpoint(path, template)
    resumed = _params00(_run(tr2, topo2, st2, xs2[3:], ys2[3:],
                             drain=True))
    assert _gap(full, resumed) == 0.0


def test_zero_checkpoint_mismatch_rejected(tmp_path):
    xs, ys = _data()
    tr_z, topo = _trainer(True)
    st = tr_z.init_state(jax.random.PRNGKey(0), xs[0, 0, 0])
    path = tr_z.save_checkpoint(str(tmp_path / "z"), st)

    tr_r, _ = _trainer(False)
    tmpl = tr_r.init_state(jax.random.PRNGKey(0), xs[0, 0, 0])
    with pytest.raises(ValueError, match="GEOMX_ZERO"):
        tr_r.load_checkpoint(path, tmpl)
    # and the reverse direction
    path_r = tr_r.save_checkpoint(str(tmp_path / "r"), tmpl)
    tmpl_z = tr_z.init_state(jax.random.PRNGKey(0), xs[0, 0, 0])
    with pytest.raises(ValueError, match="GEOMX_ZERO"):
        tr_z.load_checkpoint(path_r, tmpl_z)


def test_zero_catchup_payload_roundtrips_worker_shards():
    """catchup_payload/admit_party must carry every worker's shard, not
    W copies of worker 0's (the replicated path's (0, 0) copy would)."""
    xs, ys = _data()
    tr, topo = _trainer(True)
    st = tr.init_state(jax.random.PRNGKey(0), xs[0, 0, 0])
    st = _run(tr, topo, st, xs, ys)
    payload = tr.catchup_payload(st)
    st2 = tr.admit_party(payload)
    for a, b in zip(jax.tree.leaves(jax.tree.map(np.asarray, st.opt_state)),
                    jax.tree.leaves(jax.tree.map(np.asarray,
                                                 st2.opt_state))):
        np.testing.assert_array_equal(a, b)
    # shards really differ across workers after training (momentum has
    # per-shard content) — the thing a (0, 0) copy would have destroyed
    mom = [leaf for leaf in jax.tree.leaves(
        jax.tree.map(np.asarray, st.opt_state)) if leaf.ndim >= 3]
    assert any(np.abs(leaf[0, 0] - leaf[0, 1]).max() > 0 for leaf in mom)


# --------------------------------------------------------------------------
# wire accounting & telemetry surface
# --------------------------------------------------------------------------

def test_zero_wire_accounting_matches_traced_collectives():
    """The static ZeRO accounting (scatter (W-1)/W, gather shard*(W-1),
    per-shard dc payload) must agree with the jaxpr-derived per-chip
    bytes under the new scatter-family convention."""
    from geomx_tpu.analysis.passes import collective_wire_bytes
    from geomx_tpu.parallel.collectives import shard_map_compat
    from jax.sharding import Mesh, PartitionSpec as P

    xs, ys = _data()
    tr, topo = _trainer(True)
    st = tr.init_state(jax.random.PRNGKey(0), xs[0, 0, 0])
    params0 = jax.tree.map(lambda a: a[0, 0], st.params)
    acct = tr.sync.wire_accounting(params0)
    assert acct["zero_scatter_bytes"] > 0
    assert acct["zero_gather_bytes"] == acct["zero_scatter_bytes"]
    # dense dc tier: per-chip wire is the fp32 shard itself
    plan = tr.sync.zero_plan
    bk = plan.bucketed.zero_bucketer(jax.tree.leaves(params0))
    assert acct["dc_wire_bytes"] == 4 * sum(bk.bucket_sizes) / W_

    # trace the worker tier alone and check the convention end to end
    mesh = Mesh(np.array(jax.devices()[:W_]), ("worker",))
    bucket = jnp.zeros((bk.bucket_sizes[0],), jnp.float32)

    def f(b):
        sh = plan.scatter_bucket(b[0], "worker")
        return plan.gather_bucket(sh, "worker")[None]

    fn = shard_map_compat(f, mesh, in_specs=(P("worker"),),
                          out_specs=P("worker"))
    jx = jax.make_jaxpr(fn)(jnp.stack([bucket] * W_))
    traced = collective_wire_bytes(jx)
    n = bk.bucket_sizes[0]
    expect = 4 * n * (W_ - 1) / W_ + 4 * (n // W_) * (W_ - 1)
    assert traced == int(round(expect))


def test_zero_telemetry_gauges_and_memory_metric():
    from geomx_tpu.telemetry import get_registry, render_prometheus

    xs, ys = _data()
    tr, topo = _trainer(True, telemetry=True)
    st = tr.init_state(jax.random.PRNGKey(0), xs[0, 0, 0])
    loader = tr.make_loader(xs.reshape(-1, 8, 8, 3), ys.reshape(-1),
                            batch_size=2)
    st, _ = tr.fit(st, loader, epochs=1, log_every=1)
    text = render_prometheus()
    assert "geomx_zero_enabled" in text
    assert "geomx_zero_shard_elems" in text
    assert "geomx_step_memory_bytes" in text
    reg = get_registry()
    fam = reg.gauge("geomx_step_memory_bytes",
                    "Per-chip training-step memory by component",
                    ("component",))
    assert fam.labels(component="opt_state").value > 0


# --------------------------------------------------------------------------
# rejections
# --------------------------------------------------------------------------

@pytest.mark.parametrize("over,msg", [
    (dict(sync_mode="hfa"), "does not support the ZeRO"),
    (dict(bucket_bytes=0), "bucketed dc-tier engine"),
    (dict(multi_gps=True, bigarray_bound=128), "GEOMX_MULTI_GPS"),
    (dict(pipeline_depth=1, pipeline_dcasgd=0.04),
     "GEOMX_PIPELINE_DCASGD"),
], ids=["hfa", "no_bucketing", "multigps", "pipelined_dcasgd"])
def test_zero_invalid_compositions_rejected(over, msg):
    with pytest.raises(ValueError, match=msg):
        _trainer(True, **over)


def test_bind_zero_never_mutates_the_callers_sync():
    """bind_zero returns a bound COPY (same contract as PipelinedSync's
    shallow copy): a sync instance handed to a ZeRO trainer must stay
    usable as a replicated baseline — no zero_plan, no re-padded
    compressor, no cleared layout cache — and reusing a ZeRO-bound sync
    under a zero=False config is rejected loudly rather than running
    the replicated update against shard-shaped state."""
    topo = HiPSTopology(num_parties=P_, workers_per_party=W_)
    cfg = GeoConfig(num_parties=P_, workers_per_party=W_, zero=True)
    sync = get_sync_algorithm(cfg)
    pad_before = sync.dc_compressor.pad_to
    tr = Trainer(get_model("mlp", num_classes=10), topo, optax.sgd(0.1),
                 sync=sync, config=cfg)
    assert sync.zero_plan is None            # caller's instance untouched
    assert sync.dc_compressor.pad_to == pad_before
    assert tr.sync is not sync               # trainer bound a copy
    assert tr.sync.zero_plan is not None
    assert tr._zero_plan is tr.sync.zero_plan

    cfg_rep = GeoConfig(num_parties=P_, workers_per_party=W_, zero=False)
    with pytest.raises(ValueError, match="ZeRO-bound"):
        Trainer(get_model("mlp", num_classes=10), topo, optax.sgd(0.1),
                sync=tr.sync, config=cfg_rep)
