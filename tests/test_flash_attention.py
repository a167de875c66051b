"""Fused flash-attention kernel vs the dense jnp reference.

The kernel runs in Pallas interpret mode here (CPU suite); on TPU the
same code compiles natively.  Parity target:
`parallel/ring_attention.full_attention_reference` — the numerical
baseline every sequence-parallel mode is also tested against, so kernel
== reference chains the whole long-context stack together.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from geomx_tpu.ops.flash_attention import flash_attention, fused_attention
from geomx_tpu.parallel.ring_attention import full_attention_reference


@pytest.mark.parametrize("shape,causal", [
    ((2, 64, 4, 32), False),
    ((2, 64, 4, 32), True),
    ((1, 100, 2, 16), True),    # ragged L: padded keys must be masked
    ((2, 128, 4, 64), False),
    ((1, 16, 1, 8), True),      # L smaller than the default block
])
def test_forward_matches_dense_reference(shape, causal):
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.normal(size=shape).astype(np.float32))
               for _ in range(3))
    ref = full_attention_reference(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=5e-6, rtol=1e-5)


def test_multiple_k_blocks_accumulate_correctly():
    """The online-softmax carry across KV tiles is the whole point —
    force several k blocks per q block."""
    rng = np.random.RandomState(1)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 96, 2, 16))
                           .astype(np.float32)) for _ in range(3))
    ref = full_attention_reference(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=5e-6, rtol=1e-5)


def test_bf16_inputs_accumulate_in_f32():
    rng = np.random.RandomState(2)
    qf, kf, vf = (rng.normal(size=(1, 64, 2, 32)).astype(np.float32)
                  for _ in range(3))
    ref = full_attention_reference(jnp.asarray(qf), jnp.asarray(kf),
                                   jnp.asarray(vf))
    out = flash_attention(jnp.asarray(qf, jnp.bfloat16),
                          jnp.asarray(kf, jnp.bfloat16),
                          jnp.asarray(vf, jnp.bfloat16),
                          block_q=32, block_k=32, interpret=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), atol=0.05, rtol=0.05)


def test_gradients_match_dense_reference():
    rng = np.random.RandomState(3)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 32, 2, 16))
                           .astype(np.float32)) for _ in range(3))

    def loss_fused(q, k, v):
        return jnp.sum(fused_attention(q, k, v, True, True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(full_attention_reference(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_fused, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)


def test_fully_masked_rows_are_zero_not_nan():
    """Causal row 0 with kv padding: a row whose only unmasked key is
    itself still normalizes; rows past kv_len see only padding and must
    produce 0, never NaN (the -inf-minus--inf trap)."""
    rng = np.random.RandomState(4)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 20, 1, 8))
                           .astype(np.float32)) for _ in range(3))
    out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                          interpret=True)
    assert not bool(jnp.any(jnp.isnan(out)))


def test_lowers_to_tpu_mosaic_without_a_device():
    """Cross-platform export runs the Pallas->Mosaic lowering pass for
    the TPU target on any host — catching tiling/shape rejections (1-D
    scratch, iota rank, pl.when predicates) without TPU hardware.  Only
    Mosaic->binary compilation remains device-side."""
    from jax import export as jax_export

    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.normal(size=(2, 256, 4, 64)), jnp.float32)

    def f(q, k, v):
        return flash_attention(q, k, v, causal=True)

    exp = jax_export.export(jax.jit(f), platforms=("tpu",))(q, q, q)
    assert "tpu_custom_call" in exp.mlir_module()


@pytest.mark.parametrize("shape,causal", [
    ((2, 64, 4, 32), False),
    ((2, 64, 4, 32), True),
    ((1, 100, 2, 16), True),    # ragged L: padded q rows and k cols
    ((1, 96, 2, 16), True),     # several tiles both directions
])
def test_flash_backward_matches_dense_vjp(shape, causal):
    """flash_attention_bwd (tile-recompute from the saved lse) against
    the dense reference's vjp, for an arbitrary cotangent."""
    from geomx_tpu.ops.flash_attention import (flash_attention_bwd,
                                               flash_attention_with_lse)

    rng = np.random.RandomState(12)
    q, k, v = (jnp.asarray(rng.normal(size=shape).astype(np.float32))
               for _ in range(3))
    g = jnp.asarray(rng.normal(size=shape).astype(np.float32))

    out, lse = flash_attention_with_lse(q, k, v, causal=causal,
                                        block_q=32, block_k=32,
                                        interpret=True)
    dq, dk, dv = flash_attention_bwd(q, k, v, out, lse, g, causal=causal,
                                     block_q=32, block_k=32,
                                     interpret=True)

    def dense(q, k, v):
        return full_attention_reference(q, k, v, causal=causal)

    _, vjp = jax.vjp(dense, q, k, v)
    rq, rk, rv = vjp(g)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(rq),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(rk),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(rv),
                               atol=2e-5, rtol=2e-5)


def test_flash_backward_lowers_to_tpu_mosaic_without_a_device():
    from jax import export as jax_export

    from geomx_tpu.ops.flash_attention import (flash_attention_bwd,
                                               flash_attention_with_lse)

    rng = np.random.RandomState(13)
    q = jnp.asarray(rng.normal(size=(2, 256, 4, 64)), jnp.float32)

    def f(q, k, v, g):
        out, lse = flash_attention_with_lse(q, k, v, causal=True)
        return flash_attention_bwd(q, k, v, out, lse, g, causal=True)

    exp = jax_export.export(jax.jit(f), platforms=("tpu",))(q, q, q, q)
    assert "tpu_custom_call" in exp.mlir_module()


def _latent_inputs(seed, b, length, h, d_qk, d_v):
    rng = np.random.RandomState(seed)
    draw = lambda d: jnp.asarray(
        rng.normal(size=(b, length, h, d)).astype(np.float32))
    return draw(d_qk), draw(d_qk), draw(d_v), draw(d_v)


@pytest.mark.parametrize("length,d_qk,d_v", [
    (64, 192, 128),     # latent attention's head sizes
    (100, 24, 16),      # ragged L with unequal sizes
    (64, 16, 32),       # v wider than q and k
])
def test_unequal_head_sizes_forward_and_both_backward_kernels(length, d_qk,
                                                              d_v):
    """q and k one head size, v (and so out and dO) another, causal: the
    forward, the dq kernel and the dk/dv kernel against the dense
    reference's values and vjp."""
    from geomx_tpu.ops.flash_attention import (flash_attention_bwd,
                                               flash_attention_with_lse)
    q, k, v, g = _latent_inputs(21, 1, length, 2, d_qk, d_v)
    out, lse = flash_attention_with_lse(q, k, v, causal=True, block_q=32,
                                        block_k=32, interpret=True)
    assert out.shape == v.shape
    dense = lambda q, k, v: full_attention_reference(q, k, v, causal=True)
    ref, vjp = jax.vjp(dense, q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=5e-6, rtol=1e-5)
    grads = flash_attention_bwd(q, k, v, out, lse, g, causal=True,
                                block_q=32, block_k=32, interpret=True)
    for got, want in zip(grads, vjp(g)):
        assert got.shape == want.shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=3e-5, rtol=3e-5)


def test_unequal_head_sizes_through_fused_attention_gradients():
    q, k, v, _ = _latent_inputs(22, 2, 48, 2, 24, 16)
    fused = lambda q, k, v: jnp.sum(fused_attention(q, k, v, True, True) ** 2)
    dense = lambda q, k, v: jnp.sum(
        full_attention_reference(q, k, v, causal=True) ** 2)
    for got, want in zip(jax.grad(fused, argnums=(0, 1, 2))(q, k, v),
                         jax.grad(dense, argnums=(0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)
