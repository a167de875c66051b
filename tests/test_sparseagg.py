"""Compressed-domain aggregation suite (GEOMX_SPARSE_AGG,
compression/sparseagg.py + ops/merge_pallas.py + the server-side sparse
merge — docs/performance.md "Compressed-domain aggregation").

Layers of evidence, all on CPU:

- *merge kernel parity*: the Pallas sorted-index segment merge in
  interpret mode is bit-identical to the jnp combining tree, and both
  agree with a float64 dense oracle up to summation-order tolerance;
- *dc tier*: the owner-routed sparse allreduce produces an identical
  result on every party, bit-identical between the jnp and fused
  engines, with routing overflow reinjected into error feedback;
- *lattice tier*: fp16/2bit under the gate trace ONE integer psum (no
  gather) — 2bit exactly matches the legacy sign arithmetic;
- *host tier*: the GeoPSServer sparse round merges in sorted-sender
  order bit-exactly across arrival orders, replies sparse to
  ``sparse_ok`` pulls, falls back densify-once for optimizer stores,
  and survives a durable restart;
- *default-off*: without the gate nothing changes — the legacy
  all-gather path traces with no all_to_all.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from geomx_tpu.compression.bisparse import BiSparseCompressor
from geomx_tpu.compression.fp16 import FP16Compressor
from geomx_tpu.compression.sparseagg import (merge_pairs_host,
                                             owner_route, owner_shard_size,
                                             push_slots, sparse_allreduce,
                                             sparse_wire_bytes)
from geomx_tpu.compression.twobit import TwoBitCompressor
from geomx_tpu.ops.merge_pallas import merge_sorted_pairs
from geomx_tpu.parallel.collectives import shard_map_compat
from geomx_tpu.topology import DC_AXIS


def _dc_mesh(p):
    devs = jax.devices()
    if len(devs) < p:
        pytest.skip(f"needs {p} devices")
    return Mesh(np.array(devs[:p]), (DC_AXIS,))


def _rand_pairs(rng, parties, k, n, sentinel_frac=0.15):
    vals, idx = [], []
    for _ in range(parties):
        ii = rng.choice(n, k, replace=False).astype(np.int32)
        vv = rng.normal(0, 1, k).astype(np.float32)
        drop = rng.random(k) < sentinel_frac
        ii[drop] = -1
        vv[drop] = 0.0
        vals.append(vv)
        idx.append(ii)
    return vals, idx


# ---------- merge kernel: parity + semantics ----------


@pytest.mark.parametrize("parties,k,n", [
    (2, 33, 500),     # odd sizes, non-multiple of the sublane tile
    (4, 64, 1024),
    (8, 100, 4096),   # three combining rounds
    (3, 1, 16),       # single pair per party
    # 4 x 20,000 pairs over 30,000 indices: two 64 Ki-pair kernel blocks,
    # duplicate segments straddling the block boundary (read from the
    # halo) — the size the single-invocation kernel ran out of VMEM at
    (4, 20_000, 30_000),
])
def test_merge_sorted_pairs_parity_and_oracle(rng, parties, k, n):
    vals, idx = _rand_pairs(rng, parties, k, n)
    v = jnp.asarray(np.concatenate(vals))
    i = jnp.asarray(np.concatenate(idx))
    mv_r, mi_r = jax.jit(
        lambda a, b: merge_sorted_pairs(a, b, parties))(v, i)
    mv_f, mi_f = jax.jit(lambda a, b: merge_sorted_pairs(
        a, b, parties, fused=True, interpret=True))(v, i)
    np.testing.assert_array_equal(np.asarray(mv_r), np.asarray(mv_f))
    np.testing.assert_array_equal(np.asarray(mi_r), np.asarray(mi_f))
    # dense float64 oracle: merged heads carry the exact segment sums
    dense = np.zeros(n, np.float64)
    for vv, ii in zip(vals, idx):
        m = ii >= 0
        np.add.at(dense, ii[m], vv[m].astype(np.float64))
    mi, mv = np.asarray(mi_r), np.asarray(mv_r)
    valid = mi >= 0
    assert len(np.unique(mi[valid])) == valid.sum()  # unique indices
    got = np.zeros(n, np.float64)
    got[mi[valid]] = mv[valid]
    np.testing.assert_allclose(got, dense, atol=1e-5)


def test_merge_all_sentinels_and_all_duplicates():
    # every pair a sentinel -> all-sentinel output
    v = jnp.zeros((8,), jnp.float32)
    i = jnp.full((8,), -1, jnp.int32)
    mv, mi = merge_sorted_pairs(v, i, 4)
    assert (np.asarray(mi) == -1).all() and (np.asarray(mv) == 0).all()
    # every pair the SAME index -> one head with the full tree sum
    v = jnp.asarray(np.arange(1.0, 9.0, dtype=np.float32))
    i = jnp.full((8,), 7, jnp.int32)
    mv, mi = merge_sorted_pairs(v, i, 8)
    mi = np.asarray(mi)
    assert (mi >= 0).sum() == 1 and mi[mi >= 0][0] == 7
    assert np.asarray(mv)[mi >= 0][0] == 36.0


def test_merge_kernel_lowers_to_tpu_mosaic_without_a_device():
    from jax import export as jax_export

    def f(a, b):
        return merge_sorted_pairs(a, b, 4, fused=True)

    exp = jax_export.export(jax.jit(f), platforms=("tpu",))(
        jnp.zeros((256,), jnp.float32), jnp.zeros((256,), jnp.int32))
    assert "tpu_custom_call" in exp.mlir_module()


# ---------- owner routing ----------


def test_owner_route_slots_and_overflow(rng):
    n, P_, k = 1000, 4, 40
    S = owner_shard_size(n, P_)
    idx = np.concatenate([
        np.arange(30, dtype=np.int32),            # 30 pairs -> owner 0
        np.full(5, -1, np.int32),                 # sentinels
        (S * 3 + np.arange(5)).astype(np.int32),  # 5 pairs -> owner 3
    ])
    vals = np.arange(k, dtype=np.float32) + 1
    slots = 8
    bv, bi, ofv, ofi = jax.jit(lambda v, i: owner_route(
        v, i, n, P_, slots))(jnp.asarray(vals), jnp.asarray(idx))
    bv, bi, ofv, ofi = map(np.asarray, (bv, bi, ofv, ofi))
    assert bv.shape == (P_, slots)
    # owner 0 kept its first 8 pairs in index order, overflowed 22
    np.testing.assert_array_equal(bi[0], np.arange(8))
    assert (bi[1] == -1).all() and (bi[2] == -1).all()
    np.testing.assert_array_equal(bi[3], np.r_[S * 3 + np.arange(5),
                                               [-1] * 3])
    over = ofi < n
    assert over.sum() == 22  # the overflow came back for EF reinjection
    np.testing.assert_array_equal(np.sort(ofi[over]), np.arange(8, 30))
    # mass conservation: routed + overflow == input (sentinels excluded)
    assert np.isclose(bv.sum() + ofv.sum(), vals[idx >= 0].sum())


def test_sparse_allreduce_overflow_reinjects_into_ef():
    """Skew every index into ONE owner range: pairs past the slot
    budget must land back in the error-feedback buffer, not vanish."""
    P_, n, k = 4, 4096, 64
    mesh = _dc_mesh(P_)
    S = owner_shard_size(n, P_)
    idx = np.arange(k, dtype=np.int32)       # all owned by party 0
    assert idx.max() < S
    vals = np.ones(k, np.float32)
    slots = push_slots(k, P_)
    assert slots < k                          # the skew really overflows

    def decomp(v, i, n_):
        ok = i >= 0
        return jnp.zeros((n_,), jnp.float32).at[
            jnp.where(ok, i, 0)].add(jnp.where(ok, v, 0.0))

    def f(vs, is_, ef):
        out, ef2 = sparse_allreduce(vs[0], is_[0], n, DC_AXIS, P_,
                                    decomp, ef_buffer=ef[0])
        return out[None], ef2[None]

    fn = shard_map_compat(f, mesh, in_specs=(P(DC_AXIS),) * 3,
                          out_specs=(P(DC_AXIS),) * 2)
    out, ef = jax.jit(fn)(
        jnp.asarray(np.tile(vals, (P_, 1))),
        jnp.asarray(np.tile(idx, (P_, 1))),
        jnp.zeros((P_, n), jnp.float32))
    out, ef = np.asarray(out), np.asarray(ef)
    # every party's overflow mass (k - slots ones) is in its EF buffer
    assert np.allclose(ef.sum(axis=1), k - slots)
    # emitted coordinates carry the exact P-party sums
    emitted = out[0] != 0
    assert emitted.sum() > 0
    np.testing.assert_allclose(out[0][emitted], P_)


# ---------- dc tier end to end ----------


def test_bsc_sparse_agg_parity_and_consistency(rng):
    P_, n = 3, 8192
    mesh = _dc_mesh(P_)
    g = jnp.asarray(rng.normal(0, 1, (P_, n)).astype(np.float32))

    def run(comp):
        def f(gs, us, vs):
            out, (u2, v2) = comp.allreduce_leaf(
                gs[0], (us[0], vs[0]), DC_AXIS, P_)
            return out[None], u2[None], v2[None]

        fn = shard_map_compat(f, mesh, in_specs=(P(DC_AXIS),) * 3,
                              out_specs=(P(DC_AXIS),) * 3)
        z = jnp.zeros((P_, n), jnp.float32)
        return [np.asarray(a) for a in jax.jit(fn)(g, z, z)]

    from geomx_tpu.ops.dispatch import kernels
    base = dict(ratio=0.01, min_sparse_size=1, sparse_agg=True)
    oj = run(BiSparseCompressor(**base))
    with kernels("interpret"):
        of = run(BiSparseCompressor(**base))
    for name, a, b in zip(("out", "u", "v"), oj, of):
        np.testing.assert_array_equal(a, b, err_msg=name)
    out = oj[0]
    for p in range(1, P_):
        np.testing.assert_array_equal(out[0], out[p])
    assert (out[0] != 0).sum() > 0


def test_bsc_default_off_keeps_gather_path():
    """Without the gate the legacy wire shape stands: all_gather on the
    pairs, no all_to_all — and wire accounting keeps the 2k*4 form."""
    from geomx_tpu.analysis.core import walk_jaxpr

    P_, n = 2, 4096
    mesh = _dc_mesh(P_)

    def trace(comp):
        def f(gs, us, vs):
            out, (u2, v2) = comp.allreduce_leaf(
                gs[0], (us[0], vs[0]), DC_AXIS, P_)
            return out[None], u2[None], v2[None]

        fn = shard_map_compat(f, mesh, in_specs=(P(DC_AXIS),) * 3,
                              out_specs=(P(DC_AXIS),) * 3)
        z = jnp.zeros((P_, n), jnp.float32)
        jx = jax.make_jaxpr(fn)(z, z, z)
        return [s.primitive for s in walk_jaxpr(jx)]

    legacy = BiSparseCompressor(ratio=0.01, min_sparse_size=1,
                                sparse_agg=False)
    prims = trace(legacy)
    assert "all_gather" in prims and "all_to_all" not in prims
    leaf = jnp.zeros((n,), jnp.float32)
    assert legacy.wire_bytes_leaf(leaf) == 2 * legacy.k_for(n) * 4
    routed = BiSparseCompressor(ratio=0.01, min_sparse_size=1,
                                sparse_agg=True)
    prims2 = trace(routed)
    assert "all_to_all" in prims2
    assert routed.wire_bytes_leaf(leaf) == sparse_wire_bytes(
        routed.k_for(n), P_)


def test_dense_fallback_counter_and_reason():
    from geomx_tpu.telemetry import get_registry

    def total():
        fam = get_registry().get("geomx_bsc_dense_fallback_total")
        if fam is None:
            return 0.0
        return dict(fam.children()).get(
            ("below_min_sparse_size",), type("z", (), {"value": 0.0})
        ).value

    before = total()
    comp = BiSparseCompressor(ratio=0.1, min_sparse_size=1 << 20)
    jax.make_jaxpr(lambda g: comp.allreduce_leaf(
        g, (), DC_AXIS, 1)[0])(jnp.zeros((128,), jnp.float32))
    assert total() == before + 1


# ---------- quantized-lattice tier ----------


def test_twobit_lattice_matches_legacy_exactly(rng):
    P_, n = 3, 2048
    mesh = _dc_mesh(P_)
    g = jnp.asarray(rng.normal(0, 1, (P_, n)).astype(np.float32))

    def run(comp):
        def f(gs, rs):
            out, r2 = comp.allreduce_leaf(gs[0], rs[0], DC_AXIS, P_)
            return out[None], r2[None]

        fn = shard_map_compat(f, mesh, in_specs=(P(DC_AXIS),) * 2,
                              out_specs=(P(DC_AXIS),) * 2)
        return [np.asarray(a) for a in
                jax.jit(fn)(g, jnp.zeros((P_, n), jnp.float32))]

    legacy = run(TwoBitCompressor(0.5, sparse_agg=False))
    lattice = run(TwoBitCompressor(0.5, sparse_agg=True))
    # the ±threshold grid sums exactly in both forms: identical bits
    np.testing.assert_array_equal(legacy[0], lattice[0])
    np.testing.assert_array_equal(legacy[1], lattice[1])


def test_fp16_lattice_shared_scale_accuracy(rng):
    P_, n = 3, 2048
    mesh = _dc_mesh(P_)
    g = rng.normal(0, 1, (P_, n)).astype(np.float32)

    def f(gs):
        out, _ = FP16Compressor(sparse_agg=True).allreduce_leaf(
            gs[0], (), DC_AXIS, P_)
        return out[None]

    fn = shard_map_compat(f, mesh, in_specs=(P(DC_AXIS),),
                          out_specs=P(DC_AXIS))
    out = np.asarray(jax.jit(fn)(jnp.asarray(g)))[0]
    # int16 lattice with P-fold headroom: relative error <= P^2/32767
    # of the negotiated scale per element (P roundings at scale/q)
    tol = np.abs(g).max() * P_ * P_ / 32767.0
    np.testing.assert_allclose(out, g.sum(0), atol=3 * tol)


def test_lattice_wire_bytes_honest():
    leaf = jnp.zeros((4096,), jnp.float32)
    assert FP16Compressor(sparse_agg=True).wire_bytes_leaf(leaf) == 8192
    assert TwoBitCompressor(0.5, sparse_agg=True).wire_bytes_leaf(
        leaf) == 4096


@pytest.mark.parametrize("make,with_state", [
    (lambda: FP16Compressor(sparse_agg=True), False),
    (lambda: TwoBitCompressor(0.5, sparse_agg=True), True),
], ids=["fp16", "2bit"])
def test_lattice_traces_an_integer_psum_and_no_gather(make, with_state):
    """The structure the tier exists for: under the gate the leaf's
    allreduce is an integer-lattice psum, and nothing is gathered."""
    from geomx_tpu.analysis.core import walk_jaxpr
    from geomx_tpu.analysis.passes import _GATHER_PRIMS

    P_, n = 3, 4096
    mesh = _dc_mesh(P_)
    comp = make()

    def f(gs, ss):
        out, s2 = comp.allreduce_leaf(
            gs[0], ss[0] if with_state else (), DC_AXIS, P_)
        return out[None], (s2[None] if with_state else gs[:0])

    fn = shard_map_compat(f, mesh, in_specs=(P(DC_AXIS),) * 2,
                          out_specs=(P(DC_AXIS),) * 2)
    z = jnp.zeros((P_, n), jnp.float32)
    sites = list(walk_jaxpr(jax.make_jaxpr(fn)(z, z)))
    int_psums = [
        s for s in sites if s.primitive in ("psum", "psum2")
        and {str(v.aval.dtype) for v in s.eqn.invars
             if hasattr(v, "aval")} & {"int8", "int16", "int32"}]
    assert len(int_psums) >= 1
    assert not [s for s in sites if s.primitive in _GATHER_PRIMS]


def test_zero_shard_streams_bit_exact_across_engines(rng):
    """ZeRO composition: the shard-sized streams of
    ``BucketedCompressor.allreduce_shards`` run the same owner-routed
    merge, outputs and EF state bit-identical between the jnp and the
    Pallas (interpret) engines."""
    from geomx_tpu.compression import BucketedCompressor
    from geomx_tpu.ops.dispatch import kernels

    P_, W = 3, 2
    mesh = _dc_mesh(P_)
    params = [jnp.asarray(rng.normal(0, 1, s).astype(np.float32))
              for s in (3000, 1100)]

    def run():
        bucketed = BucketedCompressor(
            BiSparseCompressor(ratio=0.02, min_sparse_size=1,
                               sparse_agg=True),
            bucket_bytes=64 * 1024, pad_to=128 * W)
        bk = bucketed.zero_bucketer(params)
        shards = [b[:n // W]
                  for b, n in zip(bk.flatten(params), bk.bucket_sizes)]
        state = bucketed.init_shard_state(params, W)

        def f(sh, ss):
            out, s2 = bucketed.allreduce_shards(
                [a[0] for a in sh], jax.tree.map(lambda a: a[0], ss),
                DC_AXIS, P_, bk)
            return ([a[None] for a in out],
                    jax.tree.map(lambda a: a[None], s2))

        fn = shard_map_compat(f, mesh, in_specs=(P(DC_AXIS),) * 2,
                              out_specs=(P(DC_AXIS),) * 2)

        def stack(t):
            return jax.tree.map(lambda a: jnp.stack([a] * P_), t)

        return [np.asarray(a) for a in jax.tree.leaves(
            jax.jit(fn)(stack(shards), stack(state)))]

    oj = run()
    with kernels("interpret"):
        of = run()
    assert len(oj) == len(of)
    for a, b in zip(oj, of):
        np.testing.assert_array_equal(a, b)
    assert any((a != 0).any() for a in oj)


# ---------- host-plane merge ----------


def test_merge_pairs_host_sums_duplicates_sorted_unique():
    mv, mi = merge_pairs_host([
        (np.array([1.0, 2.0], np.float32), np.array([5, 3])),
        (np.array([10.0, -1.0, 0.0], np.float32), np.array([3, 9, -1])),
    ])
    np.testing.assert_array_equal(mi, [3, 5, 9])
    np.testing.assert_array_equal(mv, [12.0, 1.0, -1.0])
    mv, mi = merge_pairs_host([])
    assert mv.size == 0 and mi.size == 0


def _pairs_payload(vals, idx):
    from geomx_tpu.compression.sparseagg import encode_pairs_payload
    return encode_pairs_payload(np.asarray(vals, np.float32),
                                np.asarray(idx))


def test_server_sparse_round_overwrite_and_sparse_pull():
    from geomx_tpu.service.client import GeoPSClient
    from geomx_tpu.service.server import GeoPSServer

    n = 64
    meta = {"comp": "bsc", "n": n, "shape": [n]}
    srv = GeoPSServer(num_workers=2, mode="sync").start()
    ca = GeoPSClient(("127.0.0.1", srv.port), sender_id=0)
    cb = GeoPSClient(("127.0.0.1", srv.port), sender_id=1)
    try:
        ca.init("w", np.zeros(n, np.float32))
        ca.push("w", _pairs_payload([2.0, 1.0], [5, 9]), meta=dict(meta))
        cb.push("w", _pairs_payload([3.0], [5]), meta=dict(meta))
        out = ca.pull("w")
        exp = np.zeros(n, np.float32)
        exp[5], exp[9] = 5.0, 1.0
        np.testing.assert_array_equal(out, exp)
        # the round is STILL sparse-pending server-side: the sparse_ok
        # pull never forced the densify
        st = srv._store["w"]
        assert st.sparse_value is not None
        # a dense read folds it lazily and agrees
        np.testing.assert_array_equal(st.value, exp)
        assert st.sparse_value is None
        ca.stop_server()
        srv.join(5)
    finally:
        ca.close()
        cb.close()


def test_server_sparse_merge_bit_exact_across_arrival_orders():
    """Satellite: the PR 11 sorted-sender bit-equality contract extended
    to compressed (value, index) rounds."""
    from geomx_tpu.service.client import GeoPSClient
    from geomx_tpu.service.server import GeoPSServer

    n = 128
    meta = {"comp": "bsc", "n": n, "shape": [n]}
    payloads = {
        0: _pairs_payload([1e8, 1.0], [3, 10]),
        1: _pairs_payload([-1e8, 2.0], [3, 20]),
        2: _pairs_payload([1.0, -1.0], [3, 10]),
    }
    from geomx_tpu.telemetry import get_registry

    def sparse_merges():
        fam = get_registry().get("geomx_server_sparse_merges_total")
        return sum(ch.value for _, ch in fam.children()) if fam else 0.0

    before = sparse_merges()
    outs = []
    for order in ((0, 1, 2), (2, 1, 0), (1, 2, 0)):
        srv = GeoPSServer(num_workers=3, mode="sync").start()
        cs = [GeoPSClient(("127.0.0.1", srv.port), sender_id=s)
              for s in range(3)]
        cs[0].init("w", np.zeros(n, np.float32))
        for s in order:
            cs[s].push("w", payloads[s], meta=dict(meta))
        outs.append(np.asarray(cs[0].pull("w")))
        cs[0].stop_server()
        for c in cs:
            c.close()
        srv.join(5)
    assert np.array_equal(outs[0], outs[1])
    assert np.array_equal(outs[0], outs[2])
    # every round was merged in pair form, never densified on arrival
    assert sparse_merges() - before >= 3


def test_densify_sums_duplicate_indices_like_legacy():
    """Nothing on the wire enforces unique indices in a push payload:
    every densify path must SUM duplicates (the legacy np.add.at
    semantics), so a mixed sparse/dense round merges the same bits as
    an all-sparse one."""
    from geomx_tpu.compression.sparseagg import densify_pairs_host
    from geomx_tpu.service.client import GeoPSClient
    from geomx_tpu.service.server import GeoPSServer

    out = densify_pairs_host(np.array([1.0, 2.0, 5.0], np.float32),
                             np.array([7, 7, -1]), 16)
    assert out[7] == 3.0 and out.sum() == 3.0
    n = 32
    srv = GeoPSServer(num_workers=2, mode="sync", accumulate=True).start()
    ca = GeoPSClient(("127.0.0.1", srv.port), sender_id=0)
    cb = GeoPSClient(("127.0.0.1", srv.port), sender_id=1)
    try:
        ca.init("w", np.zeros(n, np.float32))
        # sparse sender with a DUPLICATE index + dense sender: the
        # sparse contribution densifies at the gate and both copies of
        # index 7 must survive
        ca.push("w", _pairs_payload([1.0, 2.0], [7, 7]),
                meta={"comp": "bsc", "n": n, "shape": [n]})
        cb.push("w", np.ones(n, np.float32))
        out = ca.pull("w")
        assert out[7] == 4.0, out[:9]
        ca.stop_server()
        srv.join(5)
    finally:
        ca.close()
        cb.close()


def test_large_tensor_push_falls_back_to_dense_store():
    """The pair wire format's f32 index half is exact only below 2^24:
    a push for a bigger tensor must take the legacy densify path (the
    reply side already refuses sparse there)."""
    from geomx_tpu.service.protocol import Msg, MsgType
    from geomx_tpu.service.server import GeoPSServer, _SparsePairs

    srv = GeoPSServer(num_workers=1, mode="sync")
    try:
        small = Msg(MsgType.PUSH, key="w",
                    meta={"comp": "bsc", "n": 1 << 20, "shape": [1 << 20]},
                    array=_pairs_payload([1.0], [5]))
        assert isinstance(srv._incoming_payload(small), _SparsePairs)
        big = Msg(MsgType.PUSH, key="w",
                  meta={"comp": "bsc", "n": 1 << 24, "shape": [1 << 24]},
                  array=_pairs_payload([1.0], [5]))
        assert isinstance(srv._incoming_payload(big), np.ndarray)
    finally:
        srv._running = False
        srv._srv.close()


def test_sparse_agg_parties_pins_wire_accounting():
    from geomx_tpu.compression import get_compressor

    n = 1 << 16
    leaf = jnp.zeros((n,), jnp.float32)
    pinned = get_compressor("bsc,0.01,sparse_agg=1,sparse_agg_parties=16")
    k = pinned.k_for(n)
    assert pinned.wire_bytes_leaf(leaf) == sparse_wire_bytes(k, 16)
    # an explicit pin survives traces at other widths
    mesh = _dc_mesh(2)

    def f(gs, us, vs):
        out, _ = pinned.allreduce_leaf(gs[0], (us[0], vs[0]), DC_AXIS, 2)
        return out[None]

    fn = shard_map_compat(f, mesh, in_specs=(P(DC_AXIS),) * 3,
                          out_specs=P(DC_AXIS))
    z = jnp.zeros((2, n), jnp.float32)
    jax.make_jaxpr(fn)(z, z, z)
    assert pinned.wire_bytes_leaf(leaf) == sparse_wire_bytes(k, 16)
    # unpinned: the traced width wins
    free = get_compressor("bsc,0.01,sparse_agg=1")
    assert free.wire_bytes_leaf(leaf) == sparse_wire_bytes(k, 2)


def test_server_mixed_sparse_dense_round_falls_back_dense():
    from geomx_tpu.service.client import GeoPSClient
    from geomx_tpu.service.server import GeoPSServer

    n = 32
    srv = GeoPSServer(num_workers=2, mode="sync", accumulate=True).start()
    ca = GeoPSClient(("127.0.0.1", srv.port), sender_id=0)
    cb = GeoPSClient(("127.0.0.1", srv.port), sender_id=1)
    try:
        ca.init("w", np.zeros(n, np.float32))
        ca.push("w", _pairs_payload([4.0], [7]),
                meta={"comp": "bsc", "n": n, "shape": [n]})
        cb.push("w", np.ones(n, np.float32))   # dense sender, same round
        out = ca.pull("w")
        exp = np.ones(n, np.float32)
        exp[7] += 4.0
        np.testing.assert_array_equal(out, exp)
        ca.stop_server()
        srv.join(5)
    finally:
        ca.close()
        cb.close()


def test_sparse_pending_value_migrates_in_pair_form():
    """A sparse-pending round crosses a shard migration as O(k) pairs
    (`_snapshot_key_locked`), and the importer re-installs it LAZILY —
    no densify on either side of the move."""
    from geomx_tpu.service.client import GeoPSClient
    from geomx_tpu.service.server import GeoPSServer

    n = 64
    srv = GeoPSServer(num_workers=1, mode="sync").start()
    dst = GeoPSServer(num_workers=1, mode="sync").start()
    c = GeoPSClient(("127.0.0.1", srv.port), sender_id=0)
    c2 = GeoPSClient(("127.0.0.1", dst.port), sender_id=0)
    try:
        c.init("w", np.zeros(n, np.float32))
        c.push("w", _pairs_payload([2.0, -3.0], [5, 9]),
               meta={"comp": "bsc", "n": n, "shape": [n]})
        c.pull("w")
        with srv._lock:
            assert srv._store["w"].sparse_value is not None
            rec = srv._snapshot_key_locked("w")
        assert isinstance(rec["value"], dict) and rec["value"]["sp"]
        assert len(rec["value"]["vb"]) == 2 * 4  # O(k), not O(n)
        with dst._lock:
            dst._import_key_locked("w", rec)
            assert dst._store["w"].sparse_value is not None  # still lazy
        out = c2.pull("w")
        exp = np.zeros(n, np.float32)
        exp[5], exp[9] = 2.0, -3.0
        np.testing.assert_array_equal(out, exp)
        c.stop_server()
        c2.stop_server()
        srv.join(5)
        dst.join(5)
    finally:
        c.close()
        c2.close()


def test_server_sparse_round_durable_restart_replays(tmp_path):
    from geomx_tpu.service.client import GeoPSClient
    from geomx_tpu.service.server import GeoPSServer

    n = 48
    meta = {"comp": "bsc", "n": n, "shape": [n]}
    srv = GeoPSServer(num_workers=1, mode="sync",
                      durable_dir=str(tmp_path), durable_name="g").start()
    port = srv.port
    c = GeoPSClient(("127.0.0.1", port), sender_id=0)
    try:
        c.init("w", np.zeros(n, np.float32))
        c.push("w", _pairs_payload([2.5, -1.5], [5, 9]), meta=dict(meta))
        out1 = c.pull("w")
        c.close()
        srv.crash()
        srv2 = GeoPSServer(num_workers=1, mode="sync", port=port,
                           durable_dir=str(tmp_path),
                           durable_name="g").start()
        c2 = GeoPSClient(("127.0.0.1", port), sender_id=0)
        out2 = c2.pull("w")
        np.testing.assert_array_equal(out1, out2)
        c2.stop_server()
        c2.close()
        srv2.join(5)
    finally:
        pass
