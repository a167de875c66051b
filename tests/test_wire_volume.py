"""Wire-volume accounting: the numbers BENCH reports must match what the
implementations actually put on the inter-party links.

The reference exposes sent/received byte counters on the Van
(3rdparty/ps-lite/include/ps/internal/van.h:182-183); here the
equivalent claim is per-compressor `wire_bytes_leaf` matching the real
gathered payload of the in-graph collective, and DGT's amortized
deferral matching its actual send/drain schedule.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from geomx_tpu.compression import (BiSparseCompressor, FP16Compressor,
                                   MPQCompressor, TwoBitCompressor)
from geomx_tpu.compression.base import NoCompressor
from geomx_tpu.sync.dgt import DGTCompressor


def test_wire_bytes_match_actual_payloads():
    """Each compressor's accounting equals the bytes of the tensor its
    allreduce actually gathers across the axis."""
    n = 4096
    leaf = jnp.zeros((n,), jnp.float32)

    assert NoCompressor().wire_bytes_leaf(leaf) == n * 4

    fp16 = FP16Compressor()
    assert fp16.wire_bytes_leaf(leaf) == n * 2  # fp16 payload

    from geomx_tpu.ops.dispatch import kernels
    two = TwoBitCompressor(0.5)
    # the jnp form gathers int32 words, 16 codes each
    assert two.wire_bytes_leaf(leaf) == 4 * ((n + 15) // 16)
    with kernels("native"):
        # the kernel gathers 128 int32 words per 2048-element row
        assert two.wire_bytes_leaf(leaf) == 4 * 128 * (-(-n // 2048))

    bsc = BiSparseCompressor(ratio=0.01, min_sparse_size=1)
    k = bsc.k_for(n)
    # (values, indices) pairs: 2k floats
    assert bsc.wire_bytes_leaf(leaf) == 2 * k * 4
    vals, idx, _, _ = bsc.compress(jnp.ones((n,)), jnp.zeros((n,)),
                                   jnp.zeros((n,)))
    assert vals.size * 4 + idx.size * 4 == bsc.wire_bytes_leaf(leaf)

    mpq = MPQCompressor(ratio=0.01, size_lower_bound=2048)
    small = jnp.zeros((100,), jnp.float32)
    assert mpq.wire_bytes_leaf(small) == 100 * 2          # fp16 route
    assert mpq.wire_bytes_leaf(leaf) == 2 * bsc.k_for(n) * 4  # bsc route


def test_pipelined_wire_accounting_matches_fsa_shifted():
    """Pipelined mode moves the SAME bytes per step as synchronous FSA —
    the payload is just applied one step late.  The accounting must
    report the wrapped compressor's bytes unchanged, and the allreduce
    must visibly shift the aggregates by exactly one call."""
    from geomx_tpu.compression import BucketedCompressor, get_compressor
    from geomx_tpu.sync.pipeline import PipelinedCompressor

    tree = {"a": jnp.ones((3000,), jnp.float32),
            "b": jnp.full((513,), 2.0, jnp.float32)}

    for spec in ("none", "fp16", "2bit,0.5", "bsc,0.05", "mpq,0.05"):
        wrapped = BucketedCompressor(get_compressor(spec), 1 << 20)
        piped = PipelinedCompressor(
            BucketedCompressor(get_compressor(spec), 1 << 20))
        # bytes per step identical, one step shifted
        assert piped.wire_bytes(tree) == wrapped.wire_bytes(tree), spec
        for leaf in tree.values():
            assert (piped.wire_bytes_leaf(leaf)
                    == wrapped.wire_bytes_leaf(leaf)), spec

    # the shift itself: call k applies call k-1's aggregate (axis size 1
    # makes the "collective" the identity, so values compare directly)
    piped = PipelinedCompressor(
        BucketedCompressor(get_compressor("none"), 1 << 20))
    ref = BucketedCompressor(get_compressor("none"), 1 << 20)
    state = piped.init_state(tree)
    g1 = tree
    g2 = jax.tree.map(lambda x: x * -3.0, tree)
    out1, state = piped.allreduce(g1, state, "x", 1)
    for leaf in jax.tree.leaves(out1):
        assert np.all(np.asarray(leaf) == 0.0)  # warmup bubble
    out2, state = piped.allreduce(g2, state, "x", 1)
    expect1, _ = ref.allreduce(g1, ref.init_state(tree), "x", 1)
    for got, exp in zip(jax.tree.leaves(out2), jax.tree.leaves(expect1)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(exp))

    # the in-flight buffer lives on the bucket layout (flat fp32), so
    # checkpointed wire state and error feedback share coordinates
    bk = piped.inner._bucketer(jax.tree.leaves(tree))
    assert [b.shape for b in state["inflight"]] == [
        (n,) for n in bk.bucket_sizes]


def test_dgt_amortized_accounting_matches_schedule():
    """DGT's reported (k*(f-1)+1)/f amortized fraction is the real
    send/drain schedule: non-drain steps leave the deferred blocks in
    `pending`, every f-th step drains everything."""
    be, nb, f, k = 64, 8, 3, 0.5
    comp = DGTCompressor(block_elems=be, k=k, channels=f)
    n = be * nb
    leaf = jnp.zeros((n,), jnp.float32)
    state = comp.init_leaf_state(leaf)

    frac = (k * (f - 1) + 1.0) / f
    assert comp.wire_bytes_leaf(leaf) == int(n * 4 * frac)

    rng = np.random.RandomState(0)
    sent_elems = 0
    for step in range(1, 2 * f + 1):
        g = jnp.asarray(rng.randn(n), jnp.float32)
        before = np.asarray(state["pending"])
        out, state = comp.allreduce_leaf(g, state, "x", 1)
        pending = np.asarray(state["pending"])
        pending_blocks = (np.abs(pending.reshape(nb, be)).sum(axis=1)
                          > 0).sum()
        if step % f == 0:
            assert pending_blocks == 0, f"drain step {step} left blocks"
            sent_elems += n + int((np.abs(before) > 0).sum())
        else:
            # top round(k*nb) blocks sent; the rest deferred
            assert pending_blocks == nb - round(k * nb), (step,
                                                          pending_blocks)
            sent_elems += round(k * nb) * be
        # nothing is ever LOST: delivered + pending == pushed so far
        # (reliable DGT semantics; best-effort drops are a separate,
        # opt-in mode on the host wire)
    avg_frac = sent_elems / (2 * f * n)
    # the drain also re-sends previously-deferred mass, so the long-run
    # average the accounting reports is a (slight) overestimate bound
    assert avg_frac == pytest.approx(frac, rel=0.35)
