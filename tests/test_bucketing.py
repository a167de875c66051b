"""Bucketed flat-gradient communication engine (compression/bucketing.py):
static layout invariants, numerical equivalence with the per-leaf paths,
error-feedback round-tripping through the bucket layout, MPQ
bucket-granularity routing, the dc-tier default policy, and the
collective-count reduction the fusion exists to deliver."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from geomx_tpu.compression import (BiSparseCompressor, BucketedCompressor,
                                   FP16Compressor, GradientBucketer,
                                   MPQCompressor, NoCompressor,
                                   TwoBitCompressor, maybe_bucketed)
from geomx_tpu.parallel.collectives import shard_map_compat
from geomx_tpu.topology import DC_AXIS, WORKER_AXIS


def _tree(rng, dtype=np.float32):
    """A mixed-shape gradient pytree (several leaves -> several layouts
    within one bucket, plus enough mass for sparse selection)."""
    return {
        "conv": jnp.asarray(rng.normal(size=(3, 3, 8, 16)), dtype),
        "bias": jnp.asarray(rng.normal(size=(16,)), dtype),
        "dense": jnp.asarray(rng.normal(size=(64, 32)), dtype),
        "scale": jnp.asarray(rng.normal(size=(7,)), dtype),
    }


# ---------- GradientBucketer layout ----------

def test_bucketer_layout_invariants():
    leaves = [jax.ShapeDtypeStruct(s, jnp.float32)
              for s in [(100,), (300,), (50,), (900,), (10,)]]
    bk = GradientBucketer(leaves, bucket_bytes=512 * 4, pad_to=128)
    assert bk.capacity == 512
    # greedy fill: 100+300+50 fit; 900 overflows -> own (oversized) bucket;
    # 10 starts the next
    assert [a[0] for a in bk.assignments] == [0, 0, 0, 1, 2]
    assert [a[1] for a in bk.assignments] == [0, 100, 400, 0, 0]
    assert bk.bucket_fill == [450, 900, 10]
    # lane-friendly padding
    assert bk.bucket_sizes == [512, 1024, 128]
    assert all(s % 128 == 0 for s in bk.bucket_sizes)


def test_bucketer_flatten_unflatten_roundtrip(rng):
    tree = _tree(rng)
    leaves, treedef = jax.tree.flatten(tree)
    bk = GradientBucketer(leaves, bucket_bytes=1024 * 4)
    buckets = bk.flatten(leaves)
    assert len(buckets) == bk.num_buckets
    for b, n in zip(buckets, bk.bucket_sizes):
        assert b.shape == (n,) and b.dtype == jnp.float32
    out = treedef.unflatten(bk.unflatten(buckets))
    for k in tree:
        assert out[k].dtype == tree[k].dtype
        np.testing.assert_array_equal(np.asarray(out[k]),
                                      np.asarray(tree[k]))


def test_bucketer_preserves_16bit_dtypes(rng):
    tree = _tree(rng, dtype=jnp.bfloat16)
    leaves, treedef = jax.tree.flatten(tree)
    bk = GradientBucketer(leaves, bucket_bytes=1 << 20)
    out = treedef.unflatten(bk.unflatten(bk.flatten(leaves)))
    for k in tree:
        assert out[k].dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(out[k], np.float32),
                                      np.asarray(tree[k], np.float32))


# ---------- numerical equivalence with the per-leaf paths ----------

def _run_dc_tree_allreduce(comp, trees, topo, mesh):
    """trees: pytree of [P, ...] arrays — party p contributes row p.
    Returns (per-party outputs [P, ...], final state)."""
    example = jax.tree.map(lambda a: jnp.zeros(a.shape[1:], a.dtype), trees)
    state = comp.init_state(example)
    from geomx_tpu.train.state import replicate_tree
    st_rep = replicate_tree(state, topo, mesh)
    g_rep = jax.tree.map(
        lambda a: jnp.broadcast_to(
            a[:, None], (topo.num_parties, topo.workers_per_party)
            + a.shape[1:]),
        trees)

    def f(g, st):
        g = jax.tree.map(lambda a: a[0, 0], g)
        st = jax.tree.map(lambda a: a[0, 0], st)
        out, st2 = comp.allreduce(g, st, DC_AXIS, topo.num_parties)
        return (jax.tree.map(lambda a: a[None, None], out),
                jax.tree.map(lambda a: a[None, None], st2))

    spec = P(DC_AXIS, WORKER_AXIS)
    fn = shard_map_compat(f, mesh, in_specs=(spec, spec),
                          out_specs=(spec, spec))
    out, st = jax.jit(fn)(g_rep, st_rep)
    return out, st


@pytest.mark.parametrize("inner_fn", [
    NoCompressor,
    FP16Compressor,
    lambda: TwoBitCompressor(0.5),
], ids=["none", "fp16", "2bit"])
def test_bucketed_elementwise_paths_match_per_leaf(inner_fn, topo2x4,
                                                   mesh2x4, rng):
    """Dense/fp16/2bit are element-wise, so the fused-bucket path must be
    numerically identical to the per-leaf path across the dc axis."""
    trees = jax.tree.map(
        lambda a: jnp.stack([a, -0.5 * a + 0.1]), _tree(rng))
    out_pl, _ = _run_dc_tree_allreduce(inner_fn(), trees, topo2x4, mesh2x4)
    out_b, _ = _run_dc_tree_allreduce(
        BucketedCompressor(inner_fn(), bucket_bytes=1024 * 4),
        trees, topo2x4, mesh2x4)
    for k in out_pl:
        np.testing.assert_allclose(np.asarray(out_b[k]),
                                   np.asarray(out_pl[k]), atol=1e-6)


def test_bucketed_twobit_error_feedback_roundtrips(topo2x4, mesh2x4, rng):
    """The residual the bucketed path keeps on the flat layout must hold
    the same mass at the same (leaf, offset) coordinates as the per-leaf
    residual buffers."""
    trees = jax.tree.map(lambda a: jnp.stack([a, a * 0.3]), _tree(rng))
    comp_pl = TwoBitCompressor(0.5)
    _, st_pl = _run_dc_tree_allreduce(comp_pl, trees, topo2x4, mesh2x4)
    comp_b = BucketedCompressor(TwoBitCompressor(0.5), bucket_bytes=1024 * 4)
    _, st_b = _run_dc_tree_allreduce(comp_b, trees, topo2x4, mesh2x4)

    example = jax.tree.map(lambda a: a[0], jax.tree.map(np.asarray, trees))
    leaves, treedef = jax.tree.flatten(
        jax.tree.map(lambda a: jnp.asarray(a), example))
    bk = comp_b._bucketer(leaves)
    res_buckets = [np.asarray(s)[0, 0] for s in st_b]
    res_tree = treedef.unflatten(bk.unflatten(
        [jnp.asarray(b) for b in res_buckets]))
    for k, r_pl in st_pl.items():
        np.testing.assert_allclose(np.asarray(res_tree[k]),
                                   np.asarray(r_pl)[0, 0], atol=1e-6)


def test_bucketed_bsc_single_leaf_matches_per_leaf(rng):
    """With one leaf whose size is already lane-aligned the bucket IS the
    leaf, so global selection == per-leaf selection: outputs and (u, v)
    error-feedback state must round-trip exactly."""
    n = 1024
    g = jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
    c = BiSparseCompressor(ratio=0.05, min_sparse_size=1)
    out_pl, (u_pl, v_pl) = c.allreduce_leaf(g, c.init_leaf_state(g), "x", 1)

    bc = BucketedCompressor(
        BiSparseCompressor(ratio=0.05, min_sparse_size=1),
        bucket_bytes=n * 4)
    tree = {"w": g}
    out_b, st_b = bc.allreduce(tree, bc.init_state(tree), "x", 1)
    np.testing.assert_allclose(np.asarray(out_b["w"]), np.asarray(out_pl),
                               atol=1e-6)
    u_b, v_b = st_b[0]
    np.testing.assert_allclose(np.asarray(u_b), np.asarray(u_pl).reshape(-1),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(v_b), np.asarray(v_pl).reshape(-1),
                               atol=1e-6)


def test_bucketed_bsc_global_selection_conserves_mass(rng):
    """Multi-leaf bucketed BSC: the global top-k re-allocates slots across
    leaves, but error feedback must conserve every unit of gradient mass
    through the bucket layout (emitted + retained == pushed)."""
    tree = _tree(rng)
    bc = BucketedCompressor(
        BiSparseCompressor(ratio=0.05, min_sparse_size=1),
        bucket_bytes=1 << 20)
    out, st = bc.allreduce(tree, bc.init_state(tree), "x", 1)
    leaves, treedef = jax.tree.flatten(tree)
    bk = bc._bucketer(leaves)
    v_tree = treedef.unflatten(bk.unflatten([s[1] for s in st]))
    for k in tree:
        # first step: u = g, v = g; out = selected; v2 = unselected
        np.testing.assert_allclose(
            np.asarray(out[k]) + np.asarray(v_tree[k]),
            np.asarray(tree[k]), atol=1e-5)


# ---------- MPQ bucket-granularity routing ----------

def test_mpq_routes_at_bucket_granularity():
    """Ten 200-element leaves each route fp16 per-leaf, but their fused
    2048-element bucket crosses size_lower_bound=1000 and earns the
    sparse (BSC) path — error-feedback state appears at bucket scope."""
    leaves = {f"l{i}": jnp.zeros((200,), jnp.float32) for i in range(10)}
    mpq = MPQCompressor(ratio=0.05, size_lower_bound=1000)
    # per-leaf: every leaf is small -> fp16, no state
    for leaf in jax.tree.leaves(leaves):
        assert mpq.init_leaf_state(leaf) == ()
        assert mpq.wire_bytes_leaf(leaf) == 200 * 2
    bc = BucketedCompressor(MPQCompressor(ratio=0.05, size_lower_bound=1000),
                            bucket_bytes=1 << 20)
    st = bc.init_state(leaves)
    assert len(st) == 1
    u, v = st[0]  # BSC momentum/velocity state == the bucket took BSC
    assert u.shape == (2048,)
    k = BiSparseCompressor(ratio=0.05).k_for(2048)
    assert bc.wire_bytes(leaves) == 2 * k * 4
    out, _ = bc.allreduce(leaves, st, "x", 1)
    assert jax.tree.structure(out) == jax.tree.structure(leaves)


# ---------- wire accounting ----------

def test_bucketed_wire_bytes_no_higher_for_compressed_paths(rng):
    """BSC: the global-k fused path must not cost more wire than the
    per-leaf path (small leaves no longer fall back to dense)."""
    tree = {f"p{i}": jnp.asarray(rng.normal(size=(s,)), jnp.float32)
            for i, s in enumerate([3000, 50, 700, 12000, 9])}
    bsc = BiSparseCompressor(ratio=0.01)
    bc = BucketedCompressor(BiSparseCompressor(ratio=0.01),
                            bucket_bytes=1 << 22)
    assert bc.wire_bytes(tree) <= bsc.wire_bytes(tree)


def test_bucketed_dense_wire_overhead_bounded_by_lane_padding(rng):
    tree = _tree(rng)
    dense = NoCompressor()
    bc = BucketedCompressor(NoCompressor(), bucket_bytes=1 << 22)
    report = bc.bucket_report(tree)
    pad_bytes = sum((r["padded"] - r["elems"]) * 4 for r in report)
    assert bc.wire_bytes(tree) == dense.wire_bytes(
        jax.tree.map(lambda a: a.astype(jnp.float32), tree)) + pad_bytes
    assert pad_bytes <= 128 * 4 * len(report)


def test_bucket_report_covers_every_leaf(rng):
    tree = _tree(rng)
    bc = BucketedCompressor(FP16Compressor(), bucket_bytes=1024 * 4)
    report = bc.bucket_report(tree)
    assert sum(r["leaves"] for r in report) == len(jax.tree.leaves(tree))
    assert sum(r["elems"] for r in report) == sum(
        leaf.size for leaf in jax.tree.leaves(tree))
    assert all(r["wire_bytes"] == r["padded"] * 2 for r in report)


# ---------- the dc-tier default policy ----------

def test_fsa_buckets_dc_tier_by_default():
    from geomx_tpu.sync import FSA, MixedSync
    assert isinstance(FSA().dc_compressor, BucketedCompressor)
    assert isinstance(MixedSync().dc_compressor, BucketedCompressor)
    # explicit opt-out
    assert isinstance(FSA(bucket_bytes=0).dc_compressor, NoCompressor)
    assert isinstance(MixedSync(bucket_bytes=0).dc_compressor, NoCompressor)
    # worker tier stays per-leaf
    assert isinstance(FSA().worker_compressor, NoCompressor)


def test_hfa_buckets_global_delta_by_default():
    """HFA's K1*K2 global-delta allreduce crosses the same WAN hop as
    FSA's gradients and gets the same fused-bucket default; tree-level
    DGT (chip_smoke.py's hfa_dgt config) must still never double-wrap."""
    from geomx_tpu.sync import HFA, DGTCompressor
    assert isinstance(HFA().dc_compressor, BucketedCompressor)
    assert isinstance(HFA(bucket_bytes=0).dc_compressor, NoCompressor)
    dgt = DGTCompressor()
    assert HFA(dc_compressor=dgt).dc_compressor is dgt
    # config plumbing: GEOMX_BUCKET_BYTES reaches the HFA delta tier
    from geomx_tpu.config import GeoConfig
    from geomx_tpu.sync import get_sync_algorithm
    sync = get_sync_algorithm(GeoConfig(sync_mode="hfa",
                                        bucket_bytes=1 << 16))
    assert isinstance(sync.dc_compressor, BucketedCompressor)
    assert sync.dc_compressor.bucket_bytes == 1 << 16


def test_bucket_env_opt_out(monkeypatch):
    monkeypatch.setenv("GEOMX_BUCKET_BYTES", "0")
    from geomx_tpu.sync import FSA
    assert isinstance(FSA().dc_compressor, NoCompressor)
    assert isinstance(maybe_bucketed(NoCompressor()), NoCompressor)
    monkeypatch.setenv("GEOMX_BUCKET_BYTES", "65536")
    wrapped = maybe_bucketed(NoCompressor())
    assert isinstance(wrapped, BucketedCompressor)
    assert wrapped.bucket_bytes == 65536


def test_tree_fusing_compressors_never_double_wrap():
    from geomx_tpu.sync import DGTCompressor
    dgt = DGTCompressor()
    assert maybe_bucketed(dgt) is dgt  # tree-level DGT already fuses
    bc = BucketedCompressor(NoCompressor())
    assert maybe_bucketed(bc) is bc
    # name transparency: config checks ("none" skips the wire assert)
    # see the inner compressor through the wrapper
    assert BucketedCompressor(NoCompressor()).name == "none"
    assert BucketedCompressor(BiSparseCompressor(0.01)).name == "bsc"


def test_get_sync_algorithm_honors_config_bucket_bytes():
    from geomx_tpu.config import GeoConfig
    from geomx_tpu.sync import get_sync_algorithm
    cfg = GeoConfig(sync_mode="fsa", compression="bsc,0.01")
    sync = get_sync_algorithm(cfg)
    assert isinstance(sync.dc_compressor, BucketedCompressor)
    assert sync.dc_compressor.bucket_bytes == cfg.bucket_bytes
    cfg0 = GeoConfig(sync_mode="fsa", compression="bsc,0.01", bucket_bytes=0)
    assert isinstance(get_sync_algorithm(cfg0).dc_compressor,
                      BiSparseCompressor)


def test_multigps_keeps_per_leaf_dc_semantics():
    """build_train_step must unwrap the bucketing for the MultiGPS path:
    big leaves cross the dc tier as worker-axis shards on their own
    layout."""
    import optax
    from geomx_tpu.config import GeoConfig
    from geomx_tpu.models import GeoCNN
    from geomx_tpu.sync import FSA
    from geomx_tpu.topology import HiPSTopology
    from geomx_tpu.train import Trainer
    topo = HiPSTopology(num_parties=2, workers_per_party=4)
    cfg = GeoConfig(num_parties=2, workers_per_party=4, multi_gps=True,
                    bigarray_bound=1000)
    sync = FSA(dc_compressor=FP16Compressor())
    assert isinstance(sync.dc_compressor, BucketedCompressor)
    Trainer(GeoCNN(num_classes=10), topo, optax.sgd(0.1), sync=sync,
            config=cfg)
    assert isinstance(sync.dc_compressor, FP16Compressor)


# ---------- profiler spans ----------

def test_bucketed_allreduce_emits_per_bucket_payload_spans(rng):
    from geomx_tpu.utils.profiler import get_profiler
    prof = get_profiler()
    prof.reset()
    prof.set_state(True)
    try:
        tree = _tree(rng)
        bc = BucketedCompressor(FP16Compressor(), bucket_bytes=1024 * 4)
        bc.allreduce(tree, bc.init_state(tree), "dc", 1)
    finally:
        prof.set_state(False)
    spans = [e for e in prof._events
             if e.get("name", "").startswith("dc_allreduce/bucket")]
    assert len(spans) == len(bc.bucket_report(tree))
    for e, rep in zip(spans, bc.bucket_report(tree)):
        assert e["cat"] == "comm"
        assert e["args"]["payload_bytes"] == rep["wire_bytes"]
        assert e["args"]["elems"] == rep["elems"]
    prof.reset()


def test_bucket_span_names_are_in_a_jitted_calls_hlo(rng):
    """The same scope names reach the device: inside jit they are the
    `op_name` metadata of the bucket's instructions in the compiled HLO."""
    tree = _tree(rng)
    bc = BucketedCompressor(FP16Compressor(), bucket_bytes=1024 * 4)
    state = bc.init_state(tree)
    hlo = jax.jit(lambda t, s: bc.allreduce(t, s, "dc", 1)).lower(
        tree, state).compile().as_text()
    for rep in bc.bucket_report(tree):
        assert f"/dc_allreduce/bucket{rep['bucket']}/" in hlo
    assert "/compress/flatten/" in hlo and "/compress/unflatten/" in hlo


# ---------- end-to-end: default bucketed training == per-leaf ----------

def test_bucketed_training_matches_per_leaf_losses(topo2x4):
    """The fused dc tier must not change training math: fp16-compressed
    FSA with bucketing on vs off produces the same loss trajectory."""
    import optax
    from geomx_tpu.data.datasets import load_dataset
    from geomx_tpu.models import GeoCNN
    from geomx_tpu.sync import FSA
    from geomx_tpu.train import Trainer

    data = load_dataset("synthetic", synthetic_train_n=256)

    def run(bucket_bytes):
        sync = FSA(dc_compressor=FP16Compressor(),
                   bucket_bytes=bucket_bytes)
        trainer = Trainer(GeoCNN(num_classes=10), topo2x4, optax.sgd(0.05),
                          sync=sync)
        state = trainer.init_state(jax.random.PRNGKey(0),
                                   data["train_x"][:2])
        loader = trainer.make_loader(data["train_x"], data["train_y"], 16)
        losses = []
        for xb, yb in loader.epoch(0):
            state, metrics = trainer.train_step(state, xb, yb)
            losses.append(float(metrics["loss"]))
            if len(losses) >= 4:
                break
        return losses

    np.testing.assert_allclose(run(None), run(0), rtol=1e-5, atol=1e-6)


# ---------- the point of it all: collective launches per step ----------

@pytest.mark.parametrize(
    "spec", ["none", "fp16", "2bit,0.5", "bsc,0.01", "mpq,0.01"])
def test_collective_launch_count_drops_to_num_buckets(spec):
    """Trace the dc all-reduce on a 2-party mesh and count collective
    primitives: per-leaf launches O(num_leaves), bucketed launches
    O(num_buckets)."""
    from geomx_tpu.analysis.passes import (_traced_allreduce_jaxpr,
                                           count_collectives)
    from geomx_tpu.compression import get_compressor
    from geomx_tpu.compression.bucketing import DEFAULT_BUCKET_BYTES
    from geomx_tpu.models import get_model

    model = get_model("cnn", num_classes=10)
    params = jax.jit(lambda r, x: model.init(r, x, train=False))(
        jax.random.PRNGKey(0), jnp.zeros((2, 32, 32, 3), jnp.float32)
    )["params"]
    n_leaves = len(jax.tree.leaves(params))
    assert n_leaves > 4

    def launches(comp):
        return count_collectives(_traced_allreduce_jaxpr(comp, params))

    per_leaf = get_compressor(spec)
    bucketed = BucketedCompressor(get_compressor(spec), DEFAULT_BUCKET_BYTES)
    n_per_leaf, n_bucketed = launches(per_leaf), launches(bucketed)
    assert n_per_leaf >= n_leaves
    assert n_bucketed <= 2 * len(bucketed.init_state(params))
    assert n_bucketed < n_per_leaf
    if spec.startswith("bsc"):
        # global selection must not cost more wire than per-leaf BSC
        assert (bucketed.wire_bytes(params)
                <= per_leaf.wire_bytes(params))
