"""Pipelined WAN sync (sync/pipeline.py): staleness-1 double-buffered
dc-tier collectives.

The contract under test: step t launches the dc-tier collective on step
t's party-mean and applies step t-1's completed aggregate — so the
weight update never waits on this step's DCN round trip (the structural
fact of the DCE'd jaxpr), every
gradient is applied exactly once one step late, and the whole pipeline
(in-flight buckets, model-state buffer, DCASGD previous weights) lives
in sync_state so checkpoints resume mid-pipeline bit-exactly.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from geomx_tpu.config import GeoConfig
from geomx_tpu.data.datasets import load_dataset
from geomx_tpu.models import GeoCNN
from geomx_tpu.sync import (FSA, HFA, MixedSync, PipelinedSync,
                            get_sync_algorithm)
from geomx_tpu.sync.pipeline import PipelinedCompressor
from geomx_tpu.topology import HiPSTopology
from geomx_tpu.train import Trainer


@pytest.fixture(scope="module")
def data():
    return load_dataset("synthetic", synthetic_train_n=512)


def _make(sync, data, lr=0.05, topo=None, donate=False):
    topo = topo or HiPSTopology(num_parties=2, workers_per_party=4)
    trainer = Trainer(GeoCNN(num_classes=10), topo, optax.sgd(lr),
                      sync=sync, donate=donate)
    state = trainer.init_state(jax.random.PRNGKey(0), data["train_x"][:2])
    loader = trainer.make_loader(data["train_x"], data["train_y"], 16)
    batches = [b for b in loader.epoch(0)]
    return trainer, state, batches


def _leaf00(tree):
    return np.asarray(jax.device_get(jax.tree.leaves(tree)[0]))[0, 0]


def _params_host(state):
    return jax.tree.map(lambda a: np.asarray(jax.device_get(a))[0, 0],
                        state.params)


def test_warmup_bubble_applies_zero_aggregate(data):
    """Step 0 fills the pipeline: with plain SGD the params must not
    move, while the in-flight buffer picks up the launched aggregate."""
    trainer, state, batches = _make(PipelinedSync(FSA()), data)
    p0 = _leaf00(state.params).copy()
    state1, metrics = trainer.train_step(state, *batches[0])
    assert np.allclose(p0, _leaf00(state1.params))
    assert np.isfinite(float(metrics["loss"]))
    infl = [np.asarray(jax.device_get(b))[0, 0] for b in
            state1.sync_state["inner"]["dc_comp"]["inflight"]]
    assert any(np.any(b != 0) for b in infl), "nothing launched at step 0"


def test_staleness_one_exact_vs_synchronous(data):
    """w_{t+1} = w_t - lr*g(b_{t-1}, w_{t-1}): with plain SGD the
    pipelined trajectory is exactly reconstructible from synchronous FSA
    gradients evaluated at the right (older) weights."""
    lr = 0.05
    t_pipe, s_pipe, b = _make(PipelinedSync(FSA()), data, lr=lr)
    t_sync, s_sync, _ = _make(FSA(), data, lr=lr)

    # one synchronous step on b0 recovers g(b0, w0): w0 - lr*g0
    s_sync1, _ = t_sync.train_step(s_sync, *b[0])
    w0 = _params_host(s_pipe)
    ws1 = _params_host(s_sync1)

    s_pipe1, _ = t_pipe.train_step(s_pipe, *b[0])   # bubble: w1 = w0
    s_pipe2, _ = t_pipe.train_step(s_pipe1, *b[1])  # w2 = w0 - lr*g0
    w2 = _params_host(s_pipe2)
    jax.tree.map(lambda a, e: np.testing.assert_allclose(a, e, atol=1e-6),
                 w2, ws1)

    # w3 = w2 - lr*g(b1, w1) and w1 == w0, so g(b1, w0) measured from a
    # fresh synchronous step on b1 predicts step 3 exactly
    t_sync2, s_sync0, _ = _make(FSA(), data, lr=lr)
    s_syncb1, _ = t_sync2.train_step(s_sync0, *b[1])
    g1 = jax.tree.map(lambda a, bb: (a - bb), w0, _params_host(s_syncb1))
    expect_w3 = jax.tree.map(lambda a, g: a - g, w2, g1)
    s_pipe3, _ = t_pipe.train_step(s_pipe2, *b[2])
    jax.tree.map(lambda a, e: np.testing.assert_allclose(a, e, atol=1e-5),
                 _params_host(s_pipe3), expect_w3)


def test_replicas_stay_in_sync(data):
    trainer, state, batches = _make(PipelinedSync(FSA()), data)
    for i in range(3):
        state, _ = trainer.train_step(state, *batches[i])
    arr = np.asarray(jax.device_get(jax.tree.leaves(state.params)[0]))
    for p in range(arr.shape[0]):
        for w in range(arr.shape[1]):
            np.testing.assert_allclose(arr[p, w], arr[0, 0], atol=1e-6)


def test_checkpoint_restores_inflight_state(tmp_path, data):
    """The acceptance contract: a checkpoint taken mid-pipeline resumes
    the exact trajectory — the in-flight aggregate is state, not limbo,
    and restore does not re-trigger the warmup bubble."""
    from geomx_tpu.utils.checkpoint import load_checkpoint, save_checkpoint
    trainer, state, batches = _make(
        PipelinedSync(FSA(), dcasgd_lambda=0.04), data)
    for i in range(2):
        state, _ = trainer.train_step(state, *batches[i])
    path = save_checkpoint(str(tmp_path / "mid"), state)
    restored = load_checkpoint(path, target=state)
    cont_a, _ = trainer.train_step(state, *batches[2])
    cont_b, _ = trainer.train_step(restored, *batches[2])
    for a, bb in zip(jax.tree.leaves(jax.device_get(cont_a)),
                     jax.tree.leaves(jax.device_get(cont_b))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(bb))
    # the restored continuation moved (no silent bubble re-entry)
    assert not np.allclose(_leaf00(cont_b.params), _leaf00(state.params))


def test_drain_applies_the_inflight_aggregate(data):
    """drain_pipeline lands the last launched collective without a new
    batch: bubble step + drain == one synchronous step, and the buffer
    comes back zeroed so a later fit re-warms."""
    t_pipe, s_pipe, b = _make(PipelinedSync(FSA()), data)
    t_sync, s_sync, _ = _make(FSA(), data)
    s_sync1, _ = t_sync.train_step(s_sync, *b[0])
    s_pipe1, _ = t_pipe.train_step(s_pipe, *b[0])
    drained = t_pipe.drain_pipeline(s_pipe1)
    jax.tree.map(lambda a, e: np.testing.assert_allclose(a, e, atol=1e-6),
                 _params_host(drained), _params_host(s_sync1))
    infl = [np.asarray(jax.device_get(x))[0, 0] for x in
            drained.sync_state["inner"]["dc_comp"]["inflight"]]
    assert all(np.all(x == 0) for x in infl)
    # synchronous algorithms: drain is a no-op passthrough
    assert t_sync.drain_pipeline(s_sync1) is s_sync1


def test_model_state_double_buffered():
    """A BatchNorm model under pipelined FSA: the dc-tier stat pmean is
    double-buffered (inflight_ms in sync_state), stats stay consistent
    across replicas and keep evolving."""
    import flax.linen as nn

    class BNNet(nn.Module):
        @nn.compact
        def __call__(self, x, train: bool = False):
            x = nn.Conv(8, (3, 3))(x)
            x = nn.BatchNorm(use_running_average=not train,
                             momentum=0.9)(x)
            x = nn.relu(x).reshape((x.shape[0], -1))
            return nn.Dense(10)(x)

    topo = HiPSTopology(num_parties=2, workers_per_party=2)
    trainer = Trainer(BNNet(), topo, optax.sgd(0.05),
                      sync=PipelinedSync(FSA()), donate=False)
    rng = np.random.RandomState(0)
    x = (rng.rand(2, 2, 4, 8, 8, 3) * 255).astype(np.uint8)
    y = rng.randint(0, 10, size=(2, 2, 4)).astype(np.int32)
    state = trainer.init_state(jax.random.PRNGKey(0), x[0, 0, :2])
    assert "inflight_ms" in state.sync_state
    sharding = topo.batch_sharding(trainer.mesh)
    xb, yb = jax.device_put(x, sharding), jax.device_put(y, sharding)
    ms0 = _leaf00(state.model_state).copy()
    for _ in range(3):
        state, _ = trainer.train_step(state, xb, yb)
    arr = np.asarray(jax.device_get(jax.tree.leaves(state.model_state)[0]))
    for p in range(2):
        for w in range(2):
            np.testing.assert_allclose(arr[p, w], arr[0, 0], atol=1e-6)
    assert not np.allclose(arr[0, 0], ms0), "BN stats never updated"
    # drain lands the parked stat aggregate: the final step's pmean,
    # otherwise left unapplied in inflight_ms
    parked = jax.tree.map(lambda a: np.asarray(jax.device_get(a))[0, 0],
                          state.sync_state["inflight_ms"])
    drained = trainer.drain_pipeline(state)
    got = jax.tree.map(lambda a: np.asarray(jax.device_get(a))[0, 0],
                       drained.model_state)
    jax.tree.map(lambda a, e: np.testing.assert_allclose(a, e, atol=1e-6),
                 got, parked)


def test_pipelined_mixed_sync_composes(data):
    """MixedSync's stale-pull machinery keeps working under pipelining
    (its dc-tier collective is the one double-buffered)."""
    sync = PipelinedSync(MixedSync(pull_interval=2, dcasgd_lambda=0.04),
                         dcasgd_lambda=0.04)
    trainer, state, batches = _make(sync, data)
    assert isinstance(sync.inner.dc_compressor, PipelinedCompressor)
    losses = []
    for i in range(4):
        state, metrics = trainer.train_step(state, *batches[i])
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(leaf) for leaf in losses)


def test_rejections_are_loud():
    # HFA: no per-step dc collective to double-buffer
    with pytest.raises(ValueError, match="fsa or.*mixed|mixed only"):
        PipelinedSync(HFA())
    with pytest.raises(ValueError):
        get_sync_algorithm(GeoConfig(sync_mode="hfa", num_parties=2,
                                     pipeline_depth=1))
    # only depth 1 exists
    with pytest.raises(ValueError, match="depth 1"):
        PipelinedSync(FSA(), depth=2)
    # double wrapping would double the staleness
    from geomx_tpu.compression.base import NoCompressor
    with pytest.raises(ValueError, match="already pipelined"):
        PipelinedCompressor(PipelinedCompressor(NoCompressor()))
    # MultiGPS consumes the dc shard in-step
    topo = HiPSTopology(num_parties=2, workers_per_party=4)
    cfg = GeoConfig(num_parties=2, workers_per_party=4, multi_gps=True,
                    pipeline_depth=1)
    with pytest.raises(ValueError, match="MULTI_GPS"):
        Trainer(GeoCNN(num_classes=10), topo, optax.sgd(0.1),
                sync=PipelinedSync(FSA()), config=cfg)


def test_wrapping_does_not_mutate_the_baseline():
    """PipelinedSync must not install its compressor on the caller's
    algorithm: an FSA used both wrapped and as the synchronous baseline
    must stay synchronous."""
    fsa = FSA()
    before = fsa.dc_compressor
    pipe = PipelinedSync(fsa)
    assert fsa.dc_compressor is before
    assert not isinstance(fsa.dc_compressor, PipelinedCompressor)
    assert isinstance(pipe.inner.dc_compressor, PipelinedCompressor)


def test_config_wiring():
    cfg = GeoConfig(num_parties=2, pipeline_depth=1, pipeline_dcasgd=0.04)
    algo = get_sync_algorithm(cfg)
    assert isinstance(algo, PipelinedSync)
    assert algo.name == "pipelined_fsa"
    assert algo.dcasgd_lambda == pytest.approx(0.04)
    assert isinstance(algo.inner.dc_compressor, PipelinedCompressor)
    # depth 0 stays synchronous
    assert isinstance(get_sync_algorithm(GeoConfig(num_parties=2)), FSA)
    # one party: nothing to pipeline — warn and stay synchronous (a
    # cluster script's exported depth must not taint a debug run)
    with pytest.warns(UserWarning, match="num_parties == 1"):
        algo1 = get_sync_algorithm(GeoConfig(num_parties=1,
                                             pipeline_depth=1))
    assert isinstance(algo1, FSA)


def test_single_axis_divides_elided():
    """1x1 topologies emit no dead x/1 divides in sync_grads (the same
    guard the MultiGPS path always had)."""
    for sync in (FSA(), MixedSync()):
        sync.num_parties = 1
        sync.workers_per_party = 1
        g = {"w": jnp.ones((8,))}
        state = sync.init_state(g)
        jaxpr = jax.make_jaxpr(
            lambda gg, ss: sync.sync_grads(gg, {"w": jnp.zeros((8,))},
                                           ss, jnp.zeros((), jnp.int32)))(
            g, state)
        prims = {e.primitive.name for e in jaxpr.jaxpr.eqns}
        assert "div" not in prims, (sync.name, prims)


@pytest.mark.parametrize("compression", ["none", "bsc,0.01"])
def test_pipelined_weight_path_waits_on_no_dc_collective(data, compression):
    """The structural fact behind the overlap: dead-code-eliminated down
    to params / opt_state / model_state, the synchronous step keeps a dc
    collective (the optimizer consumes the gradient's, the next forward
    the BatchNorm-stat pmean) and the pipelined step keeps none: its
    collectives are still launched and feed only sync_state, i.e. the
    next step.  Both account the same wire bytes."""
    from geomx_tpu.analysis.passes import weight_path_collectives

    def measure(pipeline_depth):
        sync = get_sync_algorithm(GeoConfig(
            num_parties=2, workers_per_party=4, compression=compression,
            pipeline_depth=pipeline_depth,
            pipeline_dcasgd=0.04 if pipeline_depth else 0.0))
        trainer, state, batches = _make(sync, data)
        on_path, whole = weight_path_collectives(
            trainer.train_step, state, *batches[0],
            keep=("params", "opt_state", "model_state"))
        comp = (sync.inner if pipeline_depth else sync).dc_compressor
        wire = comp.wire_bytes(jax.tree.map(lambda a: a[0, 0], state.params))
        return (sum(on_path.get("dc", {}).values()),
                sum(whole.get("dc", {}).values()), int(wire))

    sync_on_path, _, sync_wire = measure(0)
    pipe_on_path, pipe_total, pipe_wire = measure(1)
    assert sync_on_path >= 1
    assert pipe_on_path == 0
    assert pipe_total >= 1  # still launched
    assert sync_wire == pipe_wire


@pytest.mark.tier2
def test_convergence_parity_with_synchronous_fsa(data):
    """Acceptance: pipelined FSA (depth 1, DCASGD compensation) within
    1% of synchronous FSA accuracy at the same step budget on the seed
    convergence task.

    The budget runs in the pipeline's stable regime (adam 1e-3): a
    staleness-1 gradient roughly halves the stable-lr headroom (the
    classic delayed-SGD bound), which is the convergence price paid for
    taking the DCN round trip off the critical path — at a stable lr the
    trajectories match to full accuracy."""
    def fit(sync, steps=150, lr=1e-3):
        topo = HiPSTopology(num_parties=2, workers_per_party=4)
        trainer = Trainer(GeoCNN(num_classes=10), topo, optax.adam(lr),
                          sync=sync)
        state = trainer.init_state(jax.random.PRNGKey(0),
                                   data["train_x"][:2])
        loader = trainer.make_loader(data["train_x"], data["train_y"], 16)
        n = 0
        for epoch in range(100):
            for xb, yb in loader.epoch(epoch):
                state, _ = trainer.train_step(state, xb, yb)
                n += 1
                if n >= steps:
                    state = trainer.drain_pipeline(state)
                    return trainer.evaluate(state, data["test_x"],
                                            data["test_y"],
                                            batch_size=256)

    acc_sync = fit(FSA())
    acc_pipe = fit(PipelinedSync(FSA(), dcasgd_lambda=0.04))
    assert acc_pipe >= acc_sync - 0.01, (acc_pipe, acc_sync)
