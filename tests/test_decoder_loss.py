"""The blocked next-token loss (`models/decoder.blocked_cross_entropy`)
makes its gradient in its forward pass: against a plain un-blocked
`_block_losses` differentiated by JAX, at tiny sizes on the CPU: the
total, the hits, the groups' sums and the gradients in h, head and
weights; rows past a ragged run and rows with a negative label; a scalar
cotangent other than 1; what carries no gradient; and the products a
block, one un-differentiated and three under a gradient."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_checks as checks
from geomx_tpu.models import decoder

D, V = 32, 64
# rows, block, groups, weighted: a ragged last block; the same weighted;
# four runs of 18 that pad to 32 each; four runs of whole blocks; a block
# longer than the rows
CASES = [(70, 16, 1, False), (70, 16, 1, True), (72, 16, 4, True),
         (64, 16, 4, True), (40, 64, 1, False)]
COTANGENT = 0.37


def inputs(rows, dtype, weighted):
    ks = jax.random.split(jax.random.PRNGKey(rows), 4)
    labels = jax.random.randint(ks[2], (rows,), 0, V)
    labels = jnp.where(jnp.arange(rows) % 5 == 3, -1, labels)
    return (jax.random.normal(ks[0], (rows, D)).astype(dtype),
            (jax.random.normal(ks[1], (D, V)) * D ** -0.5).astype(dtype),
            labels,
            jax.random.uniform(ks[3], (rows,)) if weighted else None)


def plain(h, head, labels, weights, groups):
    """The whole rows as one block, nothing of the custom rule."""
    each, _, logits, _ = decoder._block_losses(head, h, labels)
    hit = (labels >= 0) & (jnp.argmax(logits, -1) == labels)
    per_group = lambda a: jnp.sum(
        a.astype(jnp.float32).reshape(groups, -1), axis=1)
    return (jnp.sum(each if weights is None else weights * each),
            per_group(hit), per_group(each))


def blocked(h, head, labels, block, weights, groups):
    out = decoder.blocked_cross_entropy(h, head, labels, block, weights,
                                        groups)
    return out if weights is not None else (out[0], out[1][None], None)


def close(got, want, dtype):
    """float32 to rounding; bfloat16 by the norm: the rule hands g to its
    two products in the head's dtype, as the MXU takes a float32 operand,
    where the CPU's autodiff keeps it float32."""
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    else:
        assert np.linalg.norm(got - want) <= 8e-3 * np.linalg.norm(want)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("rows, block, groups, weighted", CASES)
def test_loss_and_gradients_are_the_plain_ones(rows, block, groups,
                                               weighted, dtype):
    h, head, labels, weights = inputs(rows, dtype, weighted)
    argnums = (0, 1, 2) if weighted else (0, 1)

    def run(f, *static):
        scaled = lambda h_, head_, w_: (
            lambda out: (COTANGENT * out[0], out))(
                f(h_, head_, labels, *static, w_, groups))
        return jax.jit(jax.value_and_grad(scaled, argnums, has_aux=True))(
            h, head, weights)

    (_, got), got_grads = run(blocked, block)
    (_, want), want_grads = run(plain)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    np.testing.assert_array_equal(got[1], want[1])
    if weighted:
        np.testing.assert_allclose(got[2], want[2], rtol=1e-5)
    else:
        np.testing.assert_allclose(got[0], jnp.sum(want[2]), rtol=1e-5)
    assert [g.dtype for g in got_grads] == [g.dtype for g in want_grads]
    for got_grad, want_grad in zip(got_grads, want_grads):
        close(got_grad, want_grad, dtype)
    # without differentiation: the primal, the same numbers
    alone = jax.jit(lambda: blocked(h, head, labels, block, weights,
                                    groups))()
    np.testing.assert_allclose(alone[0], got[0], rtol=1e-6)
    np.testing.assert_array_equal(alone[1], got[1])


@pytest.mark.parametrize("weighted", [False, True],
                         ids=["unweighted", "weighted"])
def test_a_masked_row_has_a_zero_dh_and_a_padded_row_none(weighted):
    """A negative label (padding, the multi-token module's masked tails)
    leaves its row's dh at exactly zero, whatever its weight; the rows
    that pad a ragged run come back as no row at all."""
    h, head, labels, weights = inputs(70, jnp.float32, weighted)
    dh, dhead = jax.jit(jax.grad(
        lambda h_, head_: decoder.blocked_cross_entropy(
            h_, head_, labels, 16, weights, 2 if weighted else 1)[0],
        (0, 1)))(h, head)
    assert dh.shape == h.shape and dhead.shape == head.shape
    masked = np.asarray(labels) < 0
    assert masked.sum() == 14
    assert not np.any(np.asarray(dh)[masked])
    assert np.all(np.any(np.asarray(dh)[~masked], axis=1))
    # and a masked row moves nothing in the head either
    moved = jax.jit(jax.grad(
        lambda head_: decoder.blocked_cross_entropy(
            h.at[masked].set(100.0), head_, labels, 16, weights,
            2 if weighted else 1)[0]))(head)
    np.testing.assert_allclose(moved, dhead, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("output", [1, 2], ids=["hits", "sums"])
def test_the_hits_and_the_groups_sums_carry_no_gradient(output):
    """Counters: every caller puts them in `aux`.  A loss made of them
    would get zeros, not an error, so the docstring says it and this
    holds it."""
    h, head, labels, weights = inputs(64, jnp.float32, True)
    grads = jax.jit(jax.grad(
        lambda h_, head_, w_: jnp.sum(decoder.blocked_cross_entropy(
            h_, head_, labels, 16, w_, 4)[output]), (0, 1, 2)))(
                h, head, weights)
    assert all(not np.any(np.asarray(g)) for g in grads)


def products(jaxpr):
    return [e for e in checks.equations(jaxpr.jaxpr)
            if e.primitive.name == "dot_general"]


@pytest.mark.parametrize("weighted", [False, True],
                         ids=["unweighted", "weighted"])
def test_one_product_a_block_and_three_under_a_gradient(weighted):
    """The scan's body is in the jaxpr once.  Un-differentiated: the
    logits' product and nothing of the gradient.  Differentiated: the
    logits, dh and dhead, each at a block's rows, and no product outside
    the scan (the backward rule scales)."""
    h, head, labels, weights = inputs(64, jnp.float32, weighted)
    f = lambda h_, head_, w_: decoder.blocked_cross_entropy(
        h_, head_, labels, 16, w_, 4 if weighted else 1)[0]
    primal = products(jax.make_jaxpr(f)(h, head, weights))
    assert [e.outvars[0].aval.shape for e in primal] == [(16, V)]
    under_grad = products(jax.make_jaxpr(jax.grad(
        f, (0, 1, 2) if weighted else (0, 1)))(h, head, weights))
    assert sorted(e.outvars[0].aval.shape for e in under_grad) == sorted(
        [(16, V), (16, D), (D, V)])
