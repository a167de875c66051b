"""The decoder's two ops against the plain reference's equations
(`benchmark/references/kimi_linear.py`: a token-by-token recurrence, a
masked loop over experts), at tiny sizes on seeded weights: chunked KDA
(`ops/kda.py`), the held-experts layer and its shares
(`ops/held_experts.py`).  The model, its loss and `Trainer` are in
`test_kimi_linear.py`: two files, so that two workers share the time."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_checks as checks
from benchmark.references import kimi_linear as plain
from geomx_tpu.models import kimi_linear as kl
from geomx_tpu.ops.held_experts import held_experts
from geomx_tpu.ops.kda import kda_chunked, unit_lower_inverse

NX = checks.NX


def kda_inputs(seed, b, length, h, dk, dv, decay):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (b, length, h, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, length, h, dk)))
    v = jax.random.normal(ks[2], (b, length, h, dv))
    g = -decay * jax.random.uniform(ks[3], (b, length, h, dk))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, length, h)))
    return q, k, v, g, beta


def chunked_kda(q, k, v, g, beta, **kw):
    """`kda_chunked` is heads-major [B, H, L, d]; the reference's
    recurrence takes [B, L, H, d]."""
    major = lambda x: jnp.swapaxes(x, 1, 2)
    return major(kda_chunked(*map(major, (q, k, v, g, beta)), **kw))


@pytest.mark.parametrize("length,chunk,decay", [
    (128, 64, 0.07),    # whole chunks, a trained layer's decay
    (150, 64, 1.0),     # not a multiple of the chunk
    (37, 16, 5.0),      # down to exp(-5) a token, shorter than a chunk pair
    (64, 32, 5.0),
])
def test_chunked_kda_equals_the_token_recurrence(length, chunk, decay):
    """Values and the gradients of all five inputs."""
    args = kda_inputs(length, 2, length, 3, 32, 16, decay)
    weight = jnp.cos(jnp.arange(16.0))
    chunked = lambda *a: chunked_kda(*a, chunk=chunk)
    recurrent = lambda *a: plain.delta_rule_recurrence(NX, *a, block=8)
    (got, got_grads), (want, want_grads) = [
        checks.value_and_gradients(f, args, range(5), weight)
        for f in (chunked, recurrent)]
    np.testing.assert_allclose(got, want, atol=2e-6)
    for got, want in zip(got_grads, want_grads):
        scale = float(jnp.max(jnp.abs(want)))
        np.testing.assert_allclose(got, want, atol=2e-5 * scale)


def test_strong_decay_overflows_nowhere():
    """exp(-20) a token: a cumulative product's reciprocal would be
    exp(1280) inside one chunk; differences never leave (0, 1]."""
    q, k, v, g, beta = kda_inputs(3, 1, 64, 1, 16, 16, 0.0)
    out, grad = checks.value_and_gradients(
        lambda g_: chunked_kda(q, k, v, g_, beta), (g - 20.0,), 0)
    assert bool(jnp.all(jnp.isfinite(out))) and bool(
        jnp.all(jnp.isfinite(grad)))
    want = jax.jit(lambda *a: plain.delta_rule_recurrence(NX, *a))(
        q, k, v, g - 20.0, beta)
    np.testing.assert_allclose(out, want, atol=1e-6)


@pytest.mark.parametrize("n", [8, 16, 64])
def test_unit_lower_inverse(n):
    m = jnp.tril(jax.random.normal(jax.random.PRNGKey(n), (3, n, n)), -1) * 0.3
    eye = jnp.eye(n)
    np.testing.assert_allclose(
        jnp.matmul(unit_lower_inverse(m), eye + m, precision="highest"),
        jnp.broadcast_to(eye, m.shape), atol=2e-5)


# ---- the expert layer -----------------------------------------------------

HIDDEN, WIDTH, EXPERTS, TOP_K, SCALING = 24, 16, 16, 4, 2.446


def expert_layer(held, offset, rows=512):
    return kl.HeldExpertsLayer(EXPERTS, held, offset, TOP_K, WIDTH, SCALING,
                               rows=rows)


def expert_weights(seed, router_skew=None):
    """All 16 experts' weights under the layer's parameter names."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    draw = lambda key, *shape: jax.random.normal(key, shape) * shape[-2] ** -0.5
    params = {
        "router_kernel": draw(ks[0], HIDDEN, EXPERTS),
        "shared_gate_kernel": draw(ks[1], HIDDEN, WIDTH),
        "shared_up_kernel": draw(ks[2], HIDDEN, WIDTH),
        "shared_down_kernel": draw(ks[3], WIDTH, HIDDEN),
        "experts_gate_kernel": draw(ks[4], EXPERTS, HIDDEN, WIDTH),
        "experts_up_kernel": draw(ks[5], EXPERTS, HIDDEN, WIDTH),
        "experts_down_kernel": draw(ks[6], EXPERTS, WIDTH, HIDDEN)}
    if router_skew is not None:
        params["router_kernel"] = params["router_kernel"].at[
            :, router_skew].add(3.0)
    return params


def share_of(params, offset, held):
    cut = lambda name: params[name][offset:offset + held]
    return {**params, **{name: cut(name) for name in params
                         if name.startswith("experts_")}}


def shared_expert(params, x):
    return plain.swiglu(NX, x.reshape(-1, HIDDEN), params["shared_gate_kernel"],
                        params["shared_up_kernel"],
                        params["shared_down_kernel"]).reshape(x.shape)


def test_the_shares_add_up():
    """16 experts in 4 shares of 4: every share's partial result, with the
    shared expert (which every chip computes alike) counted once, equals
    the uncut reference's layer."""
    params = expert_weights(1)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 40, HIDDEN))
    reference = jax.jit(lambda p, offset: plain.moe(
        NX, x, p, offset, TOP_K, SCALING), static_argnums=1)
    arrived = checks.expert_shares_add_up(
        lambda offset: expert_layer(4, offset), reference, params, x,
        reference(params, 0), jax.jit(shared_expert)(params, x))
    assert arrived == 2 * 40 * TOP_K       # every assignment, exactly once


@pytest.mark.parametrize("rows", [512, 8])
def test_a_skewed_router_drops_nothing(rows):
    """One held expert gets (nearly) every token, far more than a kernel
    tile's rows and, at 8 rows a tile, than a pool's 128 (the loop over
    pools makes several trips and an expert's run crosses their borders):
    equal to the reference, values and gradients, dropped = 0."""
    params = share_of(expert_weights(3, router_skew=5), 4, 4)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 700, HIDDEN)) + 1.0
    layer = expert_layer(4, 4, rows)
    weight = jnp.sin(jnp.arange(float(HIDDEN)))
    (y, counts, dropped), ours = checks.value_and_gradients(
        lambda p, x_: layer.apply({"params": p}, x_), (params, x), (0, 1),
        weight)
    want, theirs = checks.value_and_gradients(
        lambda p, x_: plain.moe(NX, x_, p, 4, TOP_K, SCALING), (params, x),
        (0, 1), weight)
    assert int(counts[1]) >= 690 and int(dropped) == 0
    np.testing.assert_allclose(y, want, atol=5e-5)
    for got, want in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        np.testing.assert_allclose(
            got, want, atol=3e-5 * max(1.0, float(jnp.max(jnp.abs(want)))))


@pytest.mark.parametrize("rows", [8, 256])
def test_held_experts_with_no_assignment_at_all(rows):
    """Every token routed elsewhere: zeros out, zero gradients; the first
    pool is walked and finds nothing."""
    x = jax.random.normal(jax.random.PRNGKey(0), (12, HIDDEN))
    idx = jnp.full((12, TOP_K), 9, jnp.int32)
    w = jnp.ones((12, TOP_K))
    p = expert_weights(0)
    mats = [p[n][:2] for n in ("experts_gate_kernel", "experts_up_kernel",
                               "experts_down_kernel")]
    (y, counts, dropped), grads = checks.value_and_gradients(
        lambda x_, *m: held_experts(x_, idx, w, *m, 0, rows), (x, *mats),
        range(4))
    assert not np.any(np.asarray(y)) and not np.any(np.asarray(counts))
    assert int(dropped) == 0
    assert all(not np.any(np.asarray(g)) for g in grads)
