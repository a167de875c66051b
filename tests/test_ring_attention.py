"""Ring attention correctness vs dense attention on the virtual mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from geomx_tpu.parallel.collectives import shard_map_compat
from geomx_tpu.parallel.ring_attention import (full_attention_reference,
                                               ring_attention)


def _run_ring(q, k, v, n_shards, causal):
    devs = np.asarray(jax.devices()[:n_shards])
    mesh = Mesh(devs, axis_names=("sp",))
    spec = P(None, "sp", None, None)

    def f(ql, kl, vl):
        return ring_attention(ql, kl, vl, "sp", causal=causal)

    fn = shard_map_compat(f, mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return jax.jit(fn)(q, k, v)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("n_shards", [4, 8])
def test_ring_matches_dense(causal, n_shards):
    rng = np.random.RandomState(0)
    B, L, H, D = 2, 64, 2, 16
    q = jnp.asarray(rng.normal(size=(B, L, H, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, L, H, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, L, H, D)).astype(np.float32))
    out = _run_ring(q, k, v, n_shards, causal)
    ref = full_attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_ring_single_shard_degenerates_to_dense():
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.normal(size=(1, 16, 1, 8)).astype(np.float32))
    out = _run_ring(q, q, q, 1, causal=False)
    ref = full_attention_reference(q, q, q)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ring_attention_composes_with_hips_mesh():
    """3-D mesh: (dc, worker, sp) — geo data parallelism + sequence
    parallelism in one program."""
    devs = np.asarray(jax.devices()[:8]).reshape(2, 1, 4)
    mesh = Mesh(devs, axis_names=("dc", "worker", "sp"))
    rng = np.random.RandomState(2)
    B, L, H, D = 2, 32, 2, 8
    # distinct sequences per dc (data parallel over dc; sp shards L)
    q = jnp.asarray(rng.normal(size=(2 * B, L, H, D)).astype(np.float32))
    spec = P("dc", "sp", None, None)

    def f(ql):
        return ring_attention(ql, ql, ql, "sp", causal=True)

    fn = shard_map_compat(f, mesh, in_specs=(spec,), out_specs=spec)
    out = jax.jit(fn)(q)
    ref = full_attention_reference(q, q, q, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


# ---- Ulysses all-to-all sequence parallelism ----------------------------

def _run_ulysses(q, k, v, n_shards, causal):
    from geomx_tpu.parallel.ulysses import ulysses_attention

    devs = np.asarray(jax.devices()[:n_shards])
    mesh = Mesh(devs, axis_names=("sp",))
    spec = P(None, "sp", None, None)

    def f(ql, kl, vl):
        return ulysses_attention(ql, kl, vl, "sp", causal=causal)

    fn = shard_map_compat(f, mesh, in_specs=(spec, spec, spec),
                          out_specs=spec)
    return jax.jit(fn)(q, k, v)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("n_shards", [2, 4])
def test_ulysses_matches_dense(causal, n_shards):
    """Head/sequence all-to-all re-sharding computes exactly dense
    attention (the second canonical SP strategy next to ring)."""
    rng = np.random.RandomState(1)
    B, L, H, D = 2, 64, 4, 16   # H divisible by every n_shards
    q = jnp.asarray(rng.normal(size=(B, L, H, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, L, H, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, L, H, D)).astype(np.float32))
    out = _run_ulysses(q, k, v, n_shards, causal)
    ref = full_attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_ulysses_matches_ring():
    rng = np.random.RandomState(2)
    B, L, H, D = 1, 32, 4, 8
    q = jnp.asarray(rng.normal(size=(B, L, H, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, L, H, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, L, H, D)).astype(np.float32))
    u = _run_ulysses(q, k, v, 4, True)
    r = _run_ring(q, k, v, 4, True)
    np.testing.assert_allclose(np.asarray(u), np.asarray(r),
                               atol=2e-5, rtol=2e-5)


def test_ulysses_rejects_indivisible_heads():
    rng = np.random.RandomState(3)
    B, L, H, D = 1, 32, 3, 8    # 3 heads over 4 devices
    q = jnp.asarray(rng.normal(size=(B, L, H, D)).astype(np.float32))
    with pytest.raises(Exception, match="divisible"):
        _run_ulysses(q, q, q, 4, False)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_streaming_blocks_and_padding(causal):
    """The streaming softmax must match dense across block boundaries
    and with a padded (L % block != 0) tail."""
    from geomx_tpu.parallel.ulysses import _streaming_attention

    rng = np.random.RandomState(4)
    B, L, H, D = 2, 40, 2, 8
    q = jnp.asarray(rng.normal(size=(B, L, H, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, L, H, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, L, H, D)).astype(np.float32))
    out = _streaming_attention(q, k, v, causal, block=16)  # 3 blocks, pad 8
    ref = full_attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# fused Pallas hop (parallel/_fused_block.py), interpret mode on CPU
# ---------------------------------------------------------------------------

def _rand_state(rng, B, Lq, H, D, hops_done):
    """A mid-ring (m, l_acc, o) state: -inf/zeros before any hop, realistic
    running values after one."""
    if not hops_done:
        return (jnp.full((B, H, Lq), -jnp.inf, jnp.float32),
                jnp.zeros((B, H, Lq), jnp.float32),
                jnp.zeros((B, Lq, H, D), jnp.float32))
    m = jnp.asarray(rng.normal(size=(B, H, Lq)).astype(np.float32))
    l_acc = jnp.asarray(rng.uniform(0.5, 2.0, size=(B, H, Lq))
                    .astype(np.float32))
    o = jnp.asarray(rng.normal(size=(B, Lq, H, D)).astype(np.float32))
    return m, l_acc, o


@pytest.mark.parametrize("diag", [False, True])
@pytest.mark.parametrize("hops_done", [0, 1])
def test_fused_block_matches_jnp_block(diag, hops_done):
    from geomx_tpu.parallel._fused_block import fused_block
    from geomx_tpu.parallel.ring_attention import _block

    rng = np.random.RandomState(5)
    B, Lq, Lk, H, D = 2, 32, 32, 2, 16
    q, k, v = (jnp.asarray(rng.normal(size=(B, Lq, H, D))
                           .astype(np.float32)) for _ in range(3))
    m, l_acc, o = _rand_state(rng, B, Lq, H, D, hops_done)
    scale = 1.0 / np.sqrt(D)

    mask = jnp.tril(jnp.ones((Lq, Lk), bool)) if diag else None
    m_r, l_r, o_r = _block(q, k, v, m, l_acc, o, scale, mask)
    m_f, l_f, o_f = fused_block(q, k, v, m, l_acc, o, scale, diag, 16, True)
    np.testing.assert_allclose(np.asarray(m_f), np.asarray(m_r),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(l_f), np.asarray(l_r),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(o_f), np.asarray(o_r),
                               atol=1e-5, rtol=1e-5)


def test_fused_block_gradients_match_jnp_block():
    from geomx_tpu.parallel._fused_block import fused_block
    from geomx_tpu.parallel.ring_attention import _block

    rng = np.random.RandomState(6)
    B, Lq, H, D = 1, 16, 2, 8
    q, k, v = (jnp.asarray(rng.normal(size=(B, Lq, H, D))
                           .astype(np.float32)) for _ in range(3))
    m, l_acc, o = _rand_state(rng, B, Lq, H, D, 1)
    scale = 1.0 / np.sqrt(D)

    def loss_f(q, k, v):
        mf, lf, of = fused_block(q, k, v, m, l_acc, o, scale, True, 16, True)
        return jnp.sum(of ** 2) + jnp.sum(lf) + jnp.sum(mf)

    def loss_r(q, k, v):
        mask = jnp.tril(jnp.ones((Lq, Lq), bool))
        mr, lr, orr = _block(q, k, v, m, l_acc, o, scale, mask)
        return jnp.sum(orr ** 2) + jnp.sum(lr) + jnp.sum(mr)

    gf = jax.jit(jax.grad(loss_f, argnums=(0, 1, 2)))(q, k, v)
    gr = jax.jit(jax.grad(loss_r, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_fused_ring_matches_jnp_ring(causal):
    """The full ring with fused Pallas hops (interpret mode) against the
    jnp-hop ring AND the dense reference — inside shard_map, gradients
    included via the training-path test below."""
    rng = np.random.RandomState(7)
    B, L, H, D = 2, 64, 2, 16
    q, k, v = (jnp.asarray(rng.normal(size=(B, L, H, D))
                           .astype(np.float32)) for _ in range(3))
    devs = np.asarray(jax.devices()[:4])
    mesh = Mesh(devs, axis_names=("sp",))
    spec = P(None, "sp", None, None)

    def run(fused):
        def f(ql, kl, vl):
            return ring_attention(ql, kl, vl, "sp", causal=causal,
                                  use_fused=fused, _interpret=fused)
        fn = shard_map_compat(f, mesh, in_specs=(spec, spec, spec),
                              out_specs=spec)
        return jax.jit(fn)(q, k, v)

    out_f = run(True)
    out_j = run(False)
    ref = full_attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_j),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_fused_hop_lowers_to_tpu_mosaic_without_a_device():
    from jax import export as jax_export

    from geomx_tpu.parallel._fused_block import fused_block

    rng = np.random.RandomState(8)
    B, Lq, H, D = 2, 256, 4, 64
    q, k, v = (jnp.asarray(rng.normal(size=(B, Lq, H, D))
                           .astype(np.float32)) for _ in range(3))
    m = jnp.full((B, H, Lq), -jnp.inf, jnp.float32)
    l_acc = jnp.zeros((B, H, Lq), jnp.float32)
    o = jnp.zeros((B, Lq, H, D), jnp.float32)

    def f(q, k, v, m, l_acc, o):
        return fused_block(q, k, v, m, l_acc, o, 1.0 / np.sqrt(D), True,
                           128, False)

    exp = jax_export.export(jax.jit(f), platforms=("tpu",))(q, k, v, m, l_acc, o)
    assert "tpu_custom_call" in exp.mlir_module()


def test_fused_ring_gradients_match_jnp_ring():
    """Autodiff through fori_loop -> lax.cond -> custom_vjp hop must
    equal the all-jnp ring's gradients."""
    rng = np.random.RandomState(9)
    B, L, H, D = 1, 32, 2, 8
    q, k, v = (jnp.asarray(rng.normal(size=(B, L, H, D))
                           .astype(np.float32)) for _ in range(3))
    devs = np.asarray(jax.devices()[:4])
    mesh = Mesh(devs, axis_names=("sp",))
    spec = P(None, "sp", None, None)

    def make_loss(fused):
        def f(ql, kl, vl):
            out = ring_attention(ql, kl, vl, "sp", causal=True,
                                 use_fused=fused, _interpret=fused)
            return jnp.sum(out ** 2, keepdims=True).reshape(1, 1, 1, 1)
        fn = shard_map_compat(f, mesh, in_specs=(spec, spec, spec),
                              out_specs=P(None, "sp", None, None))
        return lambda q, k, v: jnp.sum(fn(q, k, v))

    gf = jax.jit(jax.grad(make_loss(True), argnums=(0, 1, 2)))(q, k, v)
    gj = jax.jit(jax.grad(make_loss(False), argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(gf, gj):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_fused_ulysses_matches_jnp_ulysses(causal):
    from geomx_tpu.parallel.ulysses import ulysses_attention

    rng = np.random.RandomState(10)
    B, L, H, D = 2, 64, 4, 16
    q, k, v = (jnp.asarray(rng.normal(size=(B, L, H, D))
                           .astype(np.float32)) for _ in range(3))
    devs = np.asarray(jax.devices()[:4])
    mesh = Mesh(devs, axis_names=("sp",))
    spec = P(None, "sp", None, None)

    def run(fused):
        def f(ql, kl, vl):
            return ulysses_attention(ql, kl, vl, "sp", causal=causal,
                                     use_fused=fused, _interpret=fused)
        fn = shard_map_compat(f, mesh, in_specs=(spec, spec, spec),
                              out_specs=spec)
        return jax.jit(fn)(q, k, v)

    out_f = run(True)
    out_j = run(False)
    ref = full_attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_j),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_fused_ulysses_gradients_match_jnp():
    from geomx_tpu.parallel.ulysses import ulysses_attention

    rng = np.random.RandomState(11)
    B, L, H, D = 1, 32, 4, 8
    q, k, v = (jnp.asarray(rng.normal(size=(B, L, H, D))
                           .astype(np.float32)) for _ in range(3))
    devs = np.asarray(jax.devices()[:4])
    mesh = Mesh(devs, axis_names=("sp",))
    spec = P(None, "sp", None, None)

    def make_loss(fused):
        def f(ql, kl, vl):
            out = ulysses_attention(ql, kl, vl, "sp", causal=True,
                                    use_fused=fused, _interpret=fused)
            return jnp.sum(out ** 2, keepdims=True).reshape(1, 1, 1, 1)
        fn = shard_map_compat(f, mesh, in_specs=(spec, spec, spec),
                              out_specs=P(None, "sp", None, None))
        return lambda q, k, v: jnp.sum(fn(q, k, v))

    gf = jax.jit(jax.grad(make_loss(True), argnums=(0, 1, 2)))(q, k, v)
    gj = jax.jit(jax.grad(make_loss(False), argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(gf, gj):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=2e-5)


def test_ulysses_fused_auto_gate_mirrors_ring_block_alignment():
    """The fused auto-gate must fall back to the streaming path when the
    flash kernel's padded seq block is not 8-aligned (ADVICE r5 #2):
    the kernel tiles the full post-all_to_all sequence in blocks of
    min(128, L), and Mosaic rejects non-sublane-aligned blocks — the
    same gate ring_attention applies to its hop block."""
    from geomx_tpu.parallel.ulysses import _fused_block_aligned

    # L >= 128 tiles at the 128 block: always aligned
    assert _fused_block_aligned(128)
    assert _fused_block_aligned(4096)
    assert _fused_block_aligned(129)  # block stays 128; L pads up
    # short sequences: the block IS the (padded) length
    assert _fused_block_aligned(64)
    assert _fused_block_aligned(8)
    assert not _fused_block_aligned(20)   # pads to 20, 20 % 8 != 0
    assert not _fused_block_aligned(100)  # 100 % 8 != 0
    assert not _fused_block_aligned(6)


def test_ulysses_misaligned_short_seq_runs_streaming_fallback():
    """End-to-end: a sequence whose padded block is not 8-aligned (per-
    shard 5 tokens x 4 shards = L 20) must run (auto-gate falls back to
    the jnp streaming path) and match the dense reference."""
    from geomx_tpu.parallel.ulysses import ulysses_attention

    rng = np.random.RandomState(12)
    B, L, H, D = 2, 20, 4, 16
    q, k, v = (jnp.asarray(rng.normal(size=(B, L, H, D))
                           .astype(np.float32)) for _ in range(3))
    devs = np.asarray(jax.devices()[:4])
    mesh = Mesh(devs, axis_names=("sp",))
    spec = P(None, "sp", None, None)

    def f(ql, kl, vl):
        return ulysses_attention(ql, kl, vl, "sp", causal=True)

    fn = shard_map_compat(f, mesh, in_specs=(spec, spec, spec),
                          out_specs=spec)
    out = jax.jit(fn)(q, k, v)
    ref = full_attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
