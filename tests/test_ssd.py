"""The Mamba-2 state-space scan in its chunkwise matrix form
(`ops/ssd.ssd_chunked`, through the door `ops.dispatch.ssd`) against the
token-by-token recurrence of the plain reference
(`benchmark/references/nemotron_h.state_space_recurrence`), in values and
in the gradients of all five inputs, float32, at several chunk counts,
lengths that are no multiple of the chunk, several heads a group."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_checks as checks
from benchmark.references import nemotron_h as plain
from geomx_tpu.ops import dispatch
from geomx_tpu.ops.ssd import ssd_chunked

NX = checks.NX


@functools.partial(jax.jit, static_argnames="block")
def ssd_recurrence(*args, block=8):
    """A token at a time, float32 at `highest`, in blocks of 8 tokens."""
    return plain.state_space_recurrence(NX, *args, block=block)


chunked = jax.jit(ssd_chunked, static_argnames="chunk")


def inputs(seed, b, length, heads, p, groups, n, step):
    """x, dt (around `step`), a (around -7: upstream's centre), b, c."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (b, length, heads, p))
    dt = jax.nn.softplus(jnp.log(jnp.expm1(step))
                         + jax.random.normal(ks[1], (b, length, heads)))
    a = -jnp.exp(1.96 + 0.5 * jax.random.normal(ks[2], (heads,)))
    bm = jax.random.normal(ks[3], (b, length, groups, n))
    cm = jax.random.normal(ks[4], (b, length, groups, n))
    return x, dt, a, bm, cm


CASES = [
    # length, chunk, heads, groups, step
    (128, 128, 4, 1, 0.01),     # one whole chunk, a trained layer's decay
    (256, 64, 4, 2, 0.01),      # four chunks, two groups of two heads
    (150, 64, 3, 1, 0.1),       # not a multiple of the chunk
    (37, 16, 2, 2, 1.0),        # down to exp(-7) a token, a head a group
    (96, 32, 6, 3, 0.3),
]


@pytest.mark.parametrize("length,chunk,heads,groups,step", CASES)
def test_chunked_form_equals_the_token_recurrence_in_values(
        length, chunk, heads, groups, step):
    args = inputs(length, 2, length, heads, 8, groups, 16, step)
    want = ssd_recurrence(*args)
    got = chunked(*args, chunk=chunk)
    assert got.shape == want.shape == (2, length, heads, 8)
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(got, want, atol=3e-6 * scale)
    # the recurrence's blocks are its own business
    np.testing.assert_allclose(ssd_recurrence(*args, block=64), want,
                               atol=3e-6 * scale)


@pytest.mark.parametrize("length,chunk,heads,groups,step", CASES)
def test_chunked_form_equals_the_token_recurrence_in_gradients(
        length, chunk, heads, groups, step):
    """Of x, dt, a, B and C, under a seeded weighting of the outputs."""
    args = inputs(length + 1, 2, length, heads, 8, groups, 16, step)
    weight = jax.random.normal(jax.random.PRNGKey(7), (2, length, heads, 8))
    grads = [checks.value_and_gradients(f, args, range(5), weight)[1]
             for f in (lambda *a: ssd_chunked(*a, chunk=chunk),
                       ssd_recurrence)]
    for name, got, want in zip("x dt a b c".split(), *grads):
        assert got.shape == want.shape, name
        np.testing.assert_allclose(
            got, want, atol=5e-5 * float(jnp.max(jnp.abs(want))),
            err_msg=name)


def test_the_state_starts_at_zero_and_padding_neither_writes_nor_decays():
    """A sequence cut short gives the longer one's first tokens: the tail
    the chunked form pads with is invisible, values and gradients."""
    args = inputs(3, 1, 80, 2, 8, 1, 16, 0.05)
    cut = lambda n: tuple(v if v.ndim == 1 else v[:, :n] for v in args)
    whole = chunked(*args, chunk=32)
    np.testing.assert_allclose(chunked(*cut(50), chunk=32),
                               whole[:, :50], atol=1e-6)
    # token 0 sees its own write only: y_0 = dt_0 (C_0 . B_0) x_0
    x, dt, _, b, c = args
    first = dt[:, 0, :, None] * jnp.sum(b[:, 0] * c[:, 0], -1)[:, :, None] \
        * x[:, 0]
    np.testing.assert_allclose(whole[:, 0], first, rtol=1e-5, atol=1e-6)


def test_the_door_gives_the_chunked_form_and_bf16_operands_stay_close():
    args = inputs(11, 2, 128, 4, 8, 2, 16, 0.01)
    door = jax.jit(dispatch.ssd, static_argnums=(5, 6))
    np.testing.assert_array_equal(door(*args, 32), chunked(*args, chunk=32))
    want = ssd_recurrence(*args)
    low = door(*args, 32, jnp.bfloat16)
    assert low.dtype == jnp.float32
    gap = float(jnp.max(jnp.abs(low - want)) / jnp.max(jnp.abs(want)))
    assert 1e-4 < gap < 2e-2, gap       # bf16 operands, float32 sums


def test_heads_that_are_not_whole_groups_are_refused():
    x, dt, a, b, c = inputs(5, 1, 32, 3, 8, 2, 16, 0.01)
    with pytest.raises(ValueError, match="whole groups"):
        ssd_chunked(x, dt, a, b, c, chunk=16)
