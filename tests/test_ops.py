"""Pallas kernel tests (interpret mode on CPU) — parity with the jnp
reference implementations beside them in ops/."""

import jax.numpy as jnp
import numpy as np
import pytest

from geomx_tpu.ops import dequantize_2bit, quantize_2bit


def test_quantize_2bit_roundtrip_and_error_feedback(rng):
    n = 5000  # exercises padding (not a block multiple)
    g = jnp.asarray(rng.normal(0, 0.6, n).astype(np.float32))
    r = jnp.asarray(rng.normal(0, 0.1, n).astype(np.float32))
    thr = 0.5
    packed, newr = quantize_2bit(g, r, thr, interpret=True)
    deq = dequantize_2bit(packed, n, thr, interpret=True)
    acc = np.asarray(g) + np.asarray(r)
    # codes match the threshold rule
    expect = np.where(acc >= thr, thr, np.where(acc <= -thr, -thr, 0.0))
    np.testing.assert_allclose(np.asarray(deq), expect, atol=1e-6)
    # error feedback conserves mass: deq + newr == g + r
    np.testing.assert_allclose(np.asarray(deq) + np.asarray(newr), acc,
                               atol=1e-5)


def test_quantize_2bit_packing_density():
    n = 2048
    g = jnp.ones((n,)) * 10.0
    packed, _ = quantize_2bit(g, jnp.zeros((n,)), 0.5, interpret=True)
    assert packed.size == n // 16  # 16x compression
    assert packed.dtype == jnp.int32


def test_quantize_zero_grad_all_zero_codes():
    n = 2048
    packed, newr = quantize_2bit(jnp.zeros((n,)), jnp.zeros((n,)), 0.5,
                                 interpret=True)
    assert not np.asarray(packed).any()
    assert not np.asarray(newr).any()


def test_pallas_compressor_matches_jnp_path(topo2x4, mesh2x4):
    """The pallas-backed 2-bit compressed all-reduce must produce the same
    dequantized sums as the jnp path."""
    from tests.test_compression import _run_dc_allreduce
    from geomx_tpu.compression import TwoBitCompressor

    rng = np.random.RandomState(7)
    g = rng.normal(0, 0.8, size=(2, 4096)).astype(np.float32)
    from geomx_tpu.ops.dispatch import kernels

    out_j, _ = _run_dc_allreduce(TwoBitCompressor(0.5), g, topo2x4, mesh2x4)
    with kernels("interpret"):
        out_p, _ = _run_dc_allreduce(TwoBitCompressor(0.5), g, topo2x4,
                                     mesh2x4)
    np.testing.assert_allclose(out_p, out_j, atol=1e-6)


# ---------- sampled_topk padding-sentinel semantics ----------

def sampled_select(v, k):
    """The scan against the boundary of |v| itself (u = g = 0)."""
    from geomx_tpu.ops.bsc_pallas import sampled_boundary_guv
    from geomx_tpu.ops.sampled_topk import sampled_threshold_select

    zero = jnp.zeros_like(v)
    return sampled_threshold_select(
        v, jnp.abs(v), k, sampled_boundary_guv(zero, zero, v, k))


def test_sampled_select_all_zero_input_emits_k_slots():
    from geomx_tpu.compression import BiSparseCompressor

    n, k = 4096, 40
    v = jnp.zeros((n,), jnp.float32)
    vals, idx, keep = sampled_select(v, k)
    # exactly k wire slots, regardless of input content
    assert vals.shape == (k,) and idx.shape == (k,)
    # zero boundary ties everything; the fixed buffer fills with k
    # (zero-valued) coordinates, never more
    assert int((np.asarray(idx) >= 0).sum()) == k
    assert int(np.asarray(keep).sum()) == k
    out = BiSparseCompressor(ratio=0.01, min_sparse_size=1).decompress(
        vals, idx, n)
    np.testing.assert_array_equal(np.asarray(out), np.zeros(n, np.float32))


def test_sampled_select_ties_fill_exactly_k():
    n, k = 2048, 32
    v = jnp.full((n,), -0.75, jnp.float32)  # every element tied at |thr|
    vals, idx, keep = sampled_select(v, k)
    assert vals.shape == (k,) and idx.shape == (k,)
    valid = np.asarray(idx) >= 0
    assert valid.sum() == k  # ties fill the buffer, never overflow it
    np.testing.assert_allclose(np.asarray(vals)[valid], -0.75)
    # first-k-in-index-order wins on ties (the reference's scan order)
    np.testing.assert_array_equal(np.sort(np.asarray(idx)[valid]),
                                  np.arange(k))


def test_sampled_select_n_smaller_than_k_pads_with_sentinels():
    from geomx_tpu.compression import BiSparseCompressor

    n, k = 10, 32
    rng = np.random.RandomState(3)
    g = rng.randn(n).astype(np.float32)
    v = jnp.asarray(g)
    vals, idx, keep = sampled_select(v, k)
    # still exactly k wire slots: n real coordinates + (k - n) sentinels
    assert vals.shape == (k,) and idx.shape == (k,)
    idx_np = np.asarray(idx)
    assert (idx_np >= 0).sum() == n
    assert (idx_np < 0).sum() == k - n
    np.testing.assert_array_equal(np.asarray(vals)[idx_np < 0], 0.0)
    # decompress drops the negative-index sentinels and reconstructs
    # every real coordinate
    out = BiSparseCompressor(ratio=0.5, min_sparse_size=1).decompress(
        vals, idx, n)
    np.testing.assert_allclose(np.asarray(out), g, rtol=1e-6)


def test_bsc_sampled_compress_drops_sentinels_through_decompress():
    """End-to-end through BiSparseCompressor: a sentinel-padded sampled
    payload round-trips the compress -> decompress pipe with the padding
    contributing nothing."""
    from geomx_tpu.compression import BiSparseCompressor

    n = 8192
    c = BiSparseCompressor(ratio=0.01, min_sparse_size=1)
    g = np.zeros(n, np.float32)
    g[7] = 3.0
    g[4096] = -2.0  # only 2 nonzeros; k = 82 slots mostly padding-bound
    vals, idx, u2, v2 = c.compress(jnp.asarray(g), jnp.zeros((n,)),
                                   jnp.zeros((n,)))
    k = c.k_for(n)
    assert vals.shape == (k,) and idx.shape == (k,)
    out = np.asarray(c.decompress(vals, idx, n))
    # the two real coordinates arrive; ties at zero may fill other slots
    # with zero-valued (harmless) entries, sentinels add nothing
    assert out[7] == pytest.approx(3.0)
    assert out[4096] == pytest.approx(-2.0)
    np.testing.assert_allclose(out + np.asarray(v2), g, atol=1e-6)


def test_twobit_kernels_lower_to_tpu_mosaic_without_a_device():
    """Same guard as the flash kernel's: cross-platform export runs the
    Pallas->Mosaic lowering pass for TPU on any host, so a future edit
    that breaks tiling/packing surfaces in the CPU suite, not on chip."""
    import jax
    from jax import export as jax_export

    g = jnp.asarray(np.random.RandomState(0).randn(8192), jnp.float32)
    r = jnp.zeros((8192,), jnp.float32)

    def f(g, r):
        packed, newr = quantize_2bit(g, r, 0.5)
        return dequantize_2bit(packed, 8192, 0.5), newr

    exp = jax_export.export(jax.jit(f), platforms=("tpu",))(g, r)
    assert "tpu_custom_call" in exp.mlir_module()
