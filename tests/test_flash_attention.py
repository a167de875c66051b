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


def dense_reference(q, k, v, causal, window=None):
    """`full_attention_reference`, with k and v repeated to q's heads
    (query head n reads key/value head n // group) and, under `window`,
    key j kept for query i iff 0 <= i - j < window."""
    group = q.shape[2] // k.shape[2]
    if group == 1 and window is None:
        return full_attention_reference(q, k, v, causal=causal)
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    if causal:
        back = (jnp.arange(q.shape[1])[:, None]
                - jnp.arange(k.shape[1])[None, :])
        seen = back >= 0
        if window is not None:
            seen = seen & (back < window)
        s = jnp.where(seen, s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


def dense_vjp(dense, g, *qkv):
    """The dense form's values and its pull of `g`: one compiled program."""
    def run(*a):
        out, pull = jax.vjp(dense, *a)
        return out, pull(g)
    return jax.jit(run)(*qkv)


def jitted_grads(loss):
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))


def _qkv(rng, shape, kv_heads=None):
    """q of `shape` [B, L, H, D]; k and v with `kv_heads` heads (H where
    None)."""
    b, length, h, d = shape
    kv = (b, length, kv_heads or h, d)
    return tuple(jnp.asarray(rng.normal(size=s).astype(np.float32))
                 for s in (shape, kv, kv))


# (kv heads, window) of the cases without grouped heads or a band
PLAIN = (None, None)
# grouped-query heads and the causal band: 8:1 and 2:1 groups; windows
# smaller than a block, not a multiple of a block, and longer than the
# sequence (= plain causal); 640 = 5 blocks of the plan's own 128, so
# pairs lie wholly under the band, cross its lower edge, miss it, touch
# and cross the diagonal; 64-wide heads in groups sit differently in
# their lane tiles than their key/value head does
GROUPED = [
    ((1, 64, 8, 16), True, (32, 32), (1, None)),
    ((2, 96, 4, 32), True, (32, 32), (2, 20)),
    ((1, 96, 8, 16), True, (32, 32), (1, 40)),
    ((1, 100, 4, 16), True, (32, 32), (2, 33)),
    ((1, 96, 2, 16), True, (32, 32), (None, 1000)),
    ((1, 640, 4, 64), True, (None, None), (2, 200)),
    ((1, 640, 16, 128), True, (None, None), (2, 130)),
    # one block pair is the whole sequence: ONE backward kernel sums dk
    # and dv over the groups
    ((1, 128, 4, 32), True, (None, None), (2, None)),
    ((2, 128, 8, 64), True, (None, None), (1, 50)),
    ((1, 128, 4, 32), False, (None, None), (2, None)),
]


@pytest.mark.parametrize("shape,causal,blocks,grouped", [
    ((2, 64, 4, 32), False, (32, 32), PLAIN),
    ((2, 64, 4, 32), True, (32, 32), PLAIN),
    ((1, 100, 2, 16), True, (32, 32), PLAIN),  # ragged L: padded keys masked
    ((2, 128, 4, 64), False, (32, 32), PLAIN),
    ((1, 16, 1, 8), True, (32, 32), PLAIN),    # L smaller than the given block
    # the plan's own blocks (None): the BERT cells' layer, one block pair
    # and two heads a step in float32; a padded length, one masked pair;
    # 640 = 5 blocks of 128, pairs that cross, touch and miss the diagonal
    ((1, 512, 16, 64), False, (None, None), PLAIN),
    ((2, 300, 2, 64), True, (None, None), PLAIN),
    ((1, 640, 4, 32), True, (None, None), PLAIN),
] + GROUPED)
def test_forward_matches_dense_reference(shape, causal, blocks, grouped):
    kv_heads, window = grouped
    q, k, v = _qkv(np.random.RandomState(0), shape, kv_heads)
    ref = dense_reference(q, k, v, causal, window)
    out = flash_attention(q, k, v, causal=causal, block_q=blocks[0],
                          block_k=blocks[1], interpret=True, window=window)
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

    gf = jitted_grads(loss_fused)(q, k, v)
    gr = jitted_grads(loss_ref)(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("interpret", [True, False],
                         ids=["kernels", "dense-fall-back"])
@pytest.mark.parametrize("shape,kv_heads,window", [
    ((1, 96, 8, 16), 1, 40), ((2, 64, 4, 32), 2, None),
    ((1, 64, 2, 16), None, 24), ((1, 64, 4, 16), 2, 500),
], ids=["8:1-window", "2:1", "window", "window-past-the-start"])
def test_fused_attention_grouped_and_windowed_gradients(shape, kv_heads,
                                                        window, interpret):
    """`fused_attention` with fewer key/value heads and a causal band,
    through the kernels and through the dense fall-back a CPU takes:
    values and all three gradients against the repeated-heads reference."""
    q, k, v = _qkv(np.random.RandomState(5), shape, kv_heads)
    fused = lambda q, k, v: jnp.sum(
        fused_attention(q, k, v, True, interpret, window) ** 2)
    dense = lambda q, k, v: jnp.sum(
        dense_reference(q, k, v, True, window) ** 2)
    np.testing.assert_allclose(float(jax.jit(fused)(q, k, v)),
                               float(jax.jit(dense)(q, k, v)), rtol=1e-5)
    for got, want in zip(jitted_grads(fused)(q, k, v),
                         jitted_grads(dense)(q, k, v)):
        assert got.shape == want.shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


def test_a_window_is_a_causal_band():
    q = jnp.ones((1, 32, 2, 8), jnp.float32)
    with pytest.raises(ValueError, match="causal band"):
        flash_attention(q, q, q, causal=False, window=8, interpret=True)
    three = jnp.ones((1, 32, 3, 8), jnp.float32)
    with pytest.raises(ValueError, match="whole groups"):
        flash_attention(q, three, three, interpret=True)


@pytest.mark.parametrize("block", [16, None])
def test_fully_masked_rows_are_zero_not_nan(block):
    """Causal row 0 with kv padding: a row whose only unmasked key is
    itself still normalizes; rows past kv_len see only padding and must
    produce 0, never NaN (the -inf-minus--inf trap).  The padded rows'
    lse (NEG_INF) must not reach the gradients either."""
    from geomx_tpu.ops.flash_attention import (flash_attention_bwd,
                                               flash_attention_with_lse)
    rng = np.random.RandomState(4)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 20, 1, 8))
                           .astype(np.float32)) for _ in range(3))
    out, lse = flash_attention_with_lse(q, k, v, causal=True, block_q=block,
                                        block_k=block, interpret=True)
    assert not bool(jnp.any(jnp.isnan(out)))
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(v[:, 0]),
                               atol=1e-6)   # row 0 sees key 0 alone
    for grad in flash_attention_bwd(q, k, v, out, lse, out, causal=True,
                                    block_q=block, block_k=block,
                                    interpret=True):
        assert bool(jnp.all(jnp.isfinite(grad)))


# (B, L, H, D, Dv, dtype, causal[, kv heads, window]): the Mosaic-lowering
# tests' sizes
LOWERED = {
    "f32-L256": (2, 256, 4, 64, 64, jnp.float32, True),
    # the BERT cells' layer: 16 x 512 x 16 x 64, bf16, four heads a step
    "bf16-bert": (16, 512, 16, 64, 64, jnp.bfloat16, False),
    # the decoder's latent attention: one sequence of 8,192, 192/128 heads
    "bf16-latent-L8192": (1, 8192, 32, 192, 128, jnp.bfloat16, True),
    # the second decoder's window layers: 32 query heads on 4 key/value
    # heads of 128, a band of 2,048 keys; and narrow grouped heads, which
    # take their own columns of a lane tile
    "bf16-grouped-window-L8192": (1, 8192, 32, 128, 128, jnp.bfloat16, True,
                                  4, 2048),
    "f32-grouped-64-L1024": (1, 1024, 8, 64, 64, jnp.float32, True, 2, 300),
}


def _lowered_shapes(case):
    """(q, k, v, out, lse, causal, window) of a case."""
    b, length, h, d, dv, dtype, causal = LOWERED[case][:7]
    kv_heads, window = (LOWERED[case][7:] or (h, None))
    q = jax.ShapeDtypeStruct((b, length, h, d), dtype)
    k = jax.ShapeDtypeStruct((b, length, kv_heads, d), dtype)
    v = jax.ShapeDtypeStruct((b, length, kv_heads, dv), dtype)
    out = jax.ShapeDtypeStruct((b, length, h, dv), dtype)
    lse = jax.ShapeDtypeStruct((b, h, length), jnp.float32)
    return q, k, v, out, lse, causal, window


@pytest.mark.parametrize("case", sorted(LOWERED))
def test_lowers_to_tpu_mosaic_without_a_device(case):
    """Cross-platform export runs the Pallas->Mosaic lowering pass for
    the TPU target on any host — catching tiling/shape rejections (1-D
    scratch, iota rank, pl.when predicates) without TPU hardware.  Only
    Mosaic->binary compilation remains device-side."""
    from jax import export as jax_export
    q, k, v, _, _, causal, window = _lowered_shapes(case)

    def f(q, k, v):
        return flash_attention(q, k, v, causal=causal, window=window)

    exp = jax_export.export(jax.jit(f), platforms=("tpu",))(q, k, v)
    assert "tpu_custom_call" in exp.mlir_module()


@pytest.mark.parametrize("shape,causal,blocks,grouped", [
    ((2, 64, 4, 32), False, (32, 32), PLAIN),
    ((2, 64, 4, 32), True, (32, 32), PLAIN),
    ((1, 100, 2, 16), True, (32, 32), PLAIN),  # ragged L: padded q rows, k cols
    ((1, 96, 2, 16), True, (32, 32), PLAIN),   # several tiles both directions
    # the plan's own blocks: the BERT cells' layer (ONE backward kernel,
    # delta = rowsum(P dP) inside it), the same causal, and a padded length
    ((1, 512, 16, 64), False, (None, None), PLAIN),
    ((1, 512, 4, 64), True, (None, None), PLAIN),
    ((2, 300, 2, 64), True, (None, None), PLAIN),
    # an 8k-style plan at a small size: q blocks of 128 against k blocks
    # of 64, so pairs cross, touch and miss the diagonal, and 320 = 2.5
    # q blocks pads the last one; then 5 x 5 blocks of the plan's own
    ((1, 320, 2, 32), True, (128, 64), PLAIN),
    ((1, 640, 4, 32), True, (None, None), PLAIN),
    ((1, 640, 4, 32), False, (None, None), PLAIN),
] + GROUPED)
def test_flash_backward_matches_dense_vjp(shape, causal, blocks, grouped):
    """flash_attention_bwd (tile-recompute from the saved lse) against
    the dense reference's vjp, for an arbitrary cotangent; with grouped
    heads dk and dv come back with k's and v's own heads."""
    from geomx_tpu.ops.flash_attention import (attention_plan,
                                               flash_attention_bwd,
                                               flash_attention_with_lse)

    kv_heads, window = grouped
    rng = np.random.RandomState(12)
    q, k, v = _qkv(rng, shape, kv_heads)
    g = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    if blocks == (None, None) and shape[1] <= 512:
        assert attention_plan(shape[1], shape[1], shape[2], shape[3],
                              shape[3], q.dtype, causal,
                              kv_heads=kv_heads).fused_backward

    out, lse = flash_attention_with_lse(q, k, v, causal=causal,
                                        block_q=blocks[0], block_k=blocks[1],
                                        interpret=True, window=window)
    dq, dk, dv = flash_attention_bwd(q, k, v, out, lse, g, causal=causal,
                                     block_q=blocks[0], block_k=blocks[1],
                                     interpret=True, window=window)
    assert dq.dtype == q.dtype and dk.dtype == k.dtype
    assert dk.shape == k.shape and dv.shape == v.shape

    def dense(q, k, v):
        return dense_reference(q, k, v, causal, window)

    _, (rq, rk, rv) = dense_vjp(dense, g, q, k, v)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(rq),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(rk),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(rv),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("case", sorted(LOWERED))
def test_flash_backward_lowers_to_tpu_mosaic_without_a_device(case):
    from jax import export as jax_export

    from geomx_tpu.ops.flash_attention import flash_attention_bwd
    q, k, v, out, lse, causal, window = _lowered_shapes(case)

    def f(q, k, v, out, lse, g):
        return flash_attention_bwd(q, k, v, out, lse, g, causal=causal,
                                   window=window)

    exp = jax_export.export(jax.jit(f), platforms=("tpu",))(
        q, k, v, out, lse, out)
    assert "tpu_custom_call" in exp.mlir_module()


def _latent_inputs(seed, b, length, h, d_qk, d_v):
    rng = np.random.RandomState(seed)
    draw = lambda d: jnp.asarray(
        rng.normal(size=(b, length, h, d)).astype(np.float32))
    return draw(d_qk), draw(d_qk), draw(d_v), draw(d_v)


@pytest.mark.parametrize("length,d_qk,d_v,block", [
    (64, 192, 128, 32),     # latent attention's head sizes
    (100, 24, 16, 32),      # ragged L with unequal sizes
    (64, 16, 32, 32),       # v wider than q and k
    # the decoder's plan at a small size: blocks of the plan's own (5 x 5
    # of 128, the last padded), two 192-wide heads a step in three lane
    # tiles of which the middle one is shared, 128-wide v
    (600, 192, 128, None),
])
def test_unequal_head_sizes_forward_and_both_backward_kernels(length, d_qk,
                                                              d_v, block):
    """q and k one head size, v (and so out and dO) another, causal: the
    forward, the dq kernel and the dk/dv kernel against the dense
    reference's values and vjp."""
    from geomx_tpu.ops.flash_attention import (flash_attention_bwd,
                                               flash_attention_with_lse)
    q, k, v, g = _latent_inputs(21, 1, length, 2, d_qk, d_v)
    out, lse = flash_attention_with_lse(q, k, v, causal=True, block_q=block,
                                        block_k=block, interpret=True)
    assert out.shape == v.shape
    dense = lambda q, k, v: full_attention_reference(q, k, v, causal=True)
    ref, pulled = dense_vjp(dense, g, q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=5e-6, rtol=1e-5)
    grads = flash_attention_bwd(q, k, v, out, lse, g, causal=True,
                                block_q=block, block_k=block, interpret=True)
    for got, want in zip(grads, pulled):
        assert got.shape == want.shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=3e-5, rtol=3e-5)


def test_unequal_head_sizes_through_fused_attention_gradients():
    q, k, v, _ = _latent_inputs(22, 2, 48, 2, 24, 16)
    fused = lambda q, k, v: jnp.sum(fused_attention(q, k, v, True, True) ** 2)
    dense = lambda q, k, v: jnp.sum(
        full_attention_reference(q, k, v, causal=True) ** 2)
    for got, want in zip(jitted_grads(fused)(q, k, v),
                         jitted_grads(dense)(q, k, v)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


# bf16 has 8 bits of mantissa: rounding to nearest is off by at most
# u = 2^-9 of the value.  On a bf16 call every product takes bf16 operands
# and sums in float32, so against the dense float32 form ON THE SAME
# ROUNDED INPUTS a result differs by its roundings alone, at most four on
# any path: `p` (into P V and P^T dO) or `ds` (into dS K and dS^T Q), `out`
# inside delta where delta is rowsum(dO O), and the result's own cast.
# Each is at most u of its term; the terms of a row's sum have mixed signs,
# so the sum of their errors is held to twice the largest magnitude's
# share: 4 roundings x 2 x u = 2^-6 of the reference's largest value.
# Readings: 1.3e-3 to 4.8e-3.  Rounding an operand to 4 bits less (u = 2^-5)
# reads 3e-2 and more, so the bound still tells precisions apart.
BF16_TOLERANCE = 2.0 ** -6


@pytest.mark.parametrize("b,length,h,d_qk,d_v,causal,blocks", [
    # the BERT cells' layer, four heads a step, one backward kernel
    (1, 512, 16, 64, 64, False, (None, None)),
    (1, 512, 4, 64, 64, True, (None, None)),
    # the decoder's plan at 1,024: 2 x 2 blocks of 512, two backward kernels
    (1, 1024, 2, 192, 128, True, (None, None)),
    # given blocks, honoured: pairs that cross, touch and miss the diagonal
    (2, 320, 4, 32, 32, True, (128, 64)),
], ids=["bert-layer", "bert-layer-causal", "latent-1024", "given-blocks"])
def test_bf16_operands_p_and_ds_rounded_against_dense_on_rounded_inputs(
        b, length, h, d_qk, d_v, causal, blocks):
    from geomx_tpu.ops.flash_attention import (flash_attention_bwd,
                                               flash_attention_with_lse)
    rounded = [x.astype(jnp.bfloat16)
               for x in _latent_inputs(31, b, length, h, d_qk, d_v)]
    q, k, v, g = rounded
    out, lse = flash_attention_with_lse(q, k, v, causal=causal,
                                        block_q=blocks[0], block_k=blocks[1],
                                        interpret=True)
    assert out.dtype == jnp.bfloat16 and lse.dtype == jnp.float32
    grads = flash_attention_bwd(q, k, v, out, lse, g, causal=causal,
                                block_q=blocks[0], block_k=blocks[1],
                                interpret=True)
    assert [x.dtype for x in grads] == [jnp.bfloat16] * 3
    f32 = lambda x: x.astype(jnp.float32)
    ref, pulled = dense_vjp(
        lambda q, k, v: full_attention_reference(q, k, v, causal=causal),
        f32(g), f32(q), f32(k), f32(v))
    for got, want in zip((out, *grads), (ref, *pulled)):
        gap = float(jnp.max(jnp.abs(f32(got) - want)) / jnp.max(jnp.abs(want)))
        assert gap <= BF16_TOLERANCE, gap


@pytest.mark.parametrize("args,want", [
    # the BERT cells' layer: the whole sequence one block pair, four heads
    # (two 128-lane tiles) a step, one backward kernel
    # (the last number: the dq^T it keeps, one block's)
    ((512, 512, 16, 64, 64, jnp.bfloat16, False),
     (512, 512, 4, True, 10_485_760, 524_288)),
    # the decoder's latent attention: 16 x 16 blocks of 512 (136 causal
    # pairs), two 192-wide heads a step, one backward kernel that keeps
    # 12 MiB of dq^T beside the 11.3 MB it streams
    ((8192, 8192, 32, 192, 128, jnp.bfloat16, True),
     (512, 512, 2, True, 23_855_104, 12_582_912)),
    # the timing tool's third shape
    ((2048, 2048, 16, 64, 64, jnp.bfloat16, False),
     (512, 512, 4, False, 9_437_184)),
    # float32 operands are twice as wide: half the heads a step
    ((512, 512, 16, 64, 64, jnp.float32, False),
     (512, 512, 2, True, 10_747_904, 262_144)),
    # nothing fits the budget: the smallest lane-aligned slab; the one
    # backward kernel streams 17.8 MB beside its 12 MiB under a limit of
    # its own
    ((8192, 8192, 32, 192, 128, jnp.float32, True),
     (512, 512, 2, True, 29_622_272, 12_582_912)),
    # a short ragged length is padded to whole lanes; a slab that cannot be
    # lane-aligned takes every head
    ((100, 100, 2, 24, 16, jnp.float32, True),
     (128, 128, 2, True, 753_664, 24_576)),
    # 640 = 5 x 128: the largest of 512, 256, 128 that divides it; causal,
    # so one backward kernel with five blocks of dq^T
    ((640, 640, 4, 32, 32, jnp.float32, True),
     (128, 128, 4, True, 1_769_472, 327_680)),
    # grouped-query heads (the last number: key/value heads), 32 on 4 of
    # 128: four query heads a step share one key/value head forward and in
    # dq (the running max and normaliser of eight would pass the budget);
    # a kernel that makes dk and dv takes the whole group of eight; a band
    # chooses no size; ONE backward kernel streams 15.2 MB beside the
    # group's dq^T, 32 MiB at 8,192
    ((8192, 8192, 32, 128, 128, jnp.bfloat16, True, None, None, 4),
     (512, 512, 4, True, 46_661_632, 33_554_432)),
    # one block pair, but a whole group of eight 128-wide heads does not
    # fit ONE backward kernel: two kernels
    ((512, 512, 32, 128, 128, jnp.bfloat16, True, None, None, 4),
     (512, 512, 4, False, 11_010_048)),
    # whole groups a step where they fit: 16 on 8 of 64, bf16
    ((512, 512, 16, 64, 64, jnp.bfloat16, True, None, None, 8),
     (512, 512, 8, True, 12_582_912, 1_048_576)),
], ids=["bert-bf16", "latent-bf16", "mid-bf16", "bert-f32", "latent-f32",
        "ragged", "five-blocks", "grouped-32-on-4", "grouped-one-pair",
        "grouped-whole-groups"])
def test_attention_plan_from_the_shapes(args, want):
    from geomx_tpu.ops.flash_attention import (VMEM_BUDGET, AttentionPlan,
                                               attention_plan)
    plan = attention_plan(*args)
    assert plan == AttentionPlan(*want)
    # the dq^T one backward kernel keeps past one block is beside the
    # budget; what it streams passes it at float32 192/128 and for the
    # group of eight, in calls that set their own limit
    kept = plan.resident_bytes - plan.resident_bytes // max(
        -(-args[0] // plan.block_q), 1)
    assert (plan.vmem_bytes - kept > VMEM_BUDGET) == (
        want[4] in (29_622_272, 46_661_632))
    assert args[2] % plan.heads == 0


@pytest.mark.parametrize("given", [(32, 64), (128, None), (None, 256)])
def test_attention_plan_honours_given_blocks(given):
    from geomx_tpu.ops.flash_attention import attention_plan
    plan = attention_plan(1024, 1024, 8, 64, 64, jnp.bfloat16, True, *given)
    assert plan.block_q == (given[0] or 512)
    assert plan.block_k == (given[1] or 512)
    assert not plan.fused_backward


@pytest.mark.parametrize("causal,want", [
    # q-major with k inner: (q block, k block, first + 2 last + 4 masked)
    (False, [(0, 0, 1), (0, 1, 2), (1, 0, 1), (1, 1, 2)]),
    (True, [(0, 0, 7), (1, 0, 1), (1, 1, 6)]),
])
def test_block_pairs_walk_only_what_holds_a_score(causal, want):
    from geomx_tpu.ops.flash_attention import _block_pairs
    (qi, kj, flags), bodies = _block_pairs(2, 2, 128, 128, 256, 256, causal,
                                           k_inner=True)
    assert list(zip(qi.tolist(), kj.tolist(), flags.tolist())) == want
    assert bodies == ([False, True] if causal else [False])
    # k-major for dk/dv: the diagonal pair opens each run; a padded length
    # marks the last row and column of pairs
    (qi, kj, flags), _ = _block_pairs(2, 2, 128, 128, 200, 200, causal,
                                      k_inner=False)
    assert sorted(zip(kj.tolist(), qi.tolist())) == list(
        zip(kj.tolist(), qi.tolist()))
    assert all(f & 4 for i, j, f in zip(qi, kj, flags) if i == 1 or j == 1)


def test_block_pairs_under_a_window_fall_with_the_band():
    """8,192 in blocks of 512 under a band of 2,048 keys: a q block meets
    its own k block and the four before it, 70 of the causal 136 pairs;
    the diagonal pair and the one that crosses the band's lower edge run
    the masked body; no window, or one that hides nothing, is the causal
    list."""
    from geomx_tpu.ops.flash_attention import _band, _block_pairs
    causal = _block_pairs(16, 16, 512, 512, 8192, 8192, True, True)
    assert len(causal[0][0]) == 136
    for k_inner in (True, False):
        (qi, kj, flags), bodies = _block_pairs(
            16, 16, 512, 512, 8192, 8192, True, k_inner, 2048)
        assert len(qi) == 70 and bodies == [False, True]
        assert all(0 <= i - j <= 4 for i, j in zip(qi, kj))
        assert all(bool(f & 4) == (i - j in (0, 4))
                   for i, j, f in zip(qi, kj, flags))
    assert _band(8192, True, 8192) is None and _band(None, True, 64) is None
    assert _band(2048, True, 8192) == 2048
    same = _block_pairs(16, 16, 512, 512, 8192, 8192, True, True,
                        _band(9000, True, 8192))
    assert all((a == b).all() for a, b in zip(same[0], causal[0]))
    # a window narrower than a block still keeps the diagonal pairs
    (qi, kj, _), _ = _block_pairs(4, 4, 128, 128, 512, 512, True, True, 16)
    assert list(zip(qi.tolist(), kj.tolist())) == [
        (0, 0), (1, 0), (1, 1), (2, 1), (2, 2), (3, 2), (3, 3)]


def test_fused_attention_opens_attn_core_forward_and_backward():
    """`attention_ms` reads scope `attn/core`: every instruction of the
    forward and of the backward, kernels and what surrounds them, has to
    sit under it in the compiled program (here the interpreted kernels'
    loops), nested in whatever scope the model opened."""
    from geomx_tpu.telemetry.layers import layer_of, op_layers
    from geomx_tpu.utils.profiler import profile_scope
    assert layer_of("attn/core") == "kernels"
    q = jnp.ones((1, 128, 2, 64), jnp.float32)

    def step(q, k, v):
        def loss(q, k, v):
            with jax.named_scope("model"), profile_scope("mla/attention"):
                return jnp.sum(fused_attention(q, k, v, True, True) ** 2)
        with profile_scope("step/forward_backward"):
            return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    text = jax.jit(step).lower(q, q, q).compile().as_text()
    found = {(entry.scope, entry.direction)
             for entry in op_layers(text).values() if entry.scope}
    nested = "step/forward_backward/mla/attention/attn/core"
    assert (nested, "forward") in found and (nested, "backward") in found
