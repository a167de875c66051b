"""The decoder's new mechanisms against the plain reference's equations
(`benchmark/references/kimi_linear.py`: a token-by-token recurrence, whole
softmax attention, a masked loop over experts, whole logits), at tiny
sizes on seeded weights: chunked KDA, the held-experts layer and its
shares, the blocked loss, the loss a model brings to `Trainer`."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.references import kimi_linear as plain  # noqa: E402
from benchmark.references.numerics import Numerics  # noqa: E402
from geomx_tpu.models import get_model  # noqa: E402
from geomx_tpu.models import kimi_linear as kl  # noqa: E402
from geomx_tpu.ops.held_experts import held_experts  # noqa: E402
from geomx_tpu.ops.kda import kda_chunked, unit_lower_inverse  # noqa: E402

NX = Numerics("float32")


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
    np.testing.assert_allclose(chunked(*args), recurrent(*args), atol=2e-6)
    grads = [jax.grad(lambda *a: jnp.sum(f(*a) * weight), argnums=range(5))(
        *args) for f in (chunked, recurrent)]
    for got, want in zip(*grads):
        scale = float(jnp.max(jnp.abs(want)))
        np.testing.assert_allclose(got, want, atol=2e-5 * scale)


def test_strong_decay_overflows_nowhere():
    """exp(-20) a token: a cumulative product's reciprocal would be
    exp(1280) inside one chunk; differences never leave (0, 1]."""
    q, k, v, g, beta = kda_inputs(3, 1, 64, 1, 16, 16, 0.0)
    out = chunked_kda(q, k, v, g - 20.0, beta)
    grad = jax.grad(lambda g_: jnp.sum(chunked_kda(q, k, v, g_, beta)))(
        g - 20.0)
    assert bool(jnp.all(jnp.isfinite(out))) and bool(
        jnp.all(jnp.isfinite(grad)))
    want = plain.delta_rule_recurrence(NX, q, k, v, g - 20.0, beta)
    np.testing.assert_allclose(out, want, atol=1e-6)


@pytest.mark.parametrize("n", [8, 16, 64])
def test_unit_lower_inverse(n):
    m = jnp.tril(jax.random.normal(jax.random.PRNGKey(n), (3, n, n)), -1) * 0.3
    eye = jnp.eye(n)
    np.testing.assert_allclose(
        jnp.matmul(unit_lower_inverse(m), eye + m, precision="highest"),
        jnp.broadcast_to(eye, m.shape), atol=2e-5)


def test_blocked_loss_equals_the_whole_one():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    h = jax.random.normal(ks[0], (70, 24))
    head = jax.random.normal(ks[1], (24, 50)) * 0.3
    labels = jax.random.randint(ks[2], (70,), 0, 50)

    def whole(h_, head_):
        logits = h_ @ head_
        picked = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, -1) - picked)

    blocked = lambda h_, head_: kl.blocked_cross_entropy(
        h_, head_, labels, 16)[0]       # 70 tokens: a ragged last block
    np.testing.assert_allclose(blocked(h, head), whole(h, head), rtol=1e-6)
    for got, want in zip(jax.grad(blocked, (0, 1))(h, head),
                         jax.grad(whole, (0, 1))(h, head)):
        np.testing.assert_allclose(got, want, atol=1e-5)
    hits = kl.blocked_cross_entropy(h, head, labels, 16)[1]
    assert float(hits) == float(jnp.sum(jnp.argmax(h @ head, -1) == labels))


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
    whole = plain.moe(NX, x, params, 0, TOP_K, SCALING)
    shared = shared_expert(params, x)
    total, arrived = shared, 0
    for offset in range(0, EXPERTS, 4):
        y, counts, dropped = expert_layer(4, offset).apply(
            {"params": share_of(params, offset, 4)}, x)
        # the program's share equals the reference's share
        np.testing.assert_allclose(
            y, plain.moe(NX, x, share_of(params, offset, 4), offset, TOP_K,
                         SCALING), atol=2e-5)
        total = total + (y - shared)
        arrived += int(jnp.sum(counts))
        assert int(dropped) == 0
    np.testing.assert_allclose(total, whole, atol=5e-5)
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
    y, counts, dropped = layer.apply({"params": params}, x)
    assert int(counts[1]) >= 690 and int(dropped) == 0
    np.testing.assert_allclose(
        y, plain.moe(NX, x, params, 4, TOP_K, SCALING), atol=5e-5)
    weight = jnp.sin(jnp.arange(float(HIDDEN)))
    ours = jax.grad(lambda p, x_: jnp.sum(
        layer.apply({"params": p}, x_)[0] * weight), (0, 1))(params, x)
    theirs = jax.grad(lambda p, x_: jnp.sum(
        plain.moe(NX, x_, p, 4, TOP_K, SCALING) * weight), (0, 1))(params, x)
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
    y, counts, dropped = held_experts(x, idx, w, *mats, 0, rows)
    assert not np.any(np.asarray(y)) and not np.any(np.asarray(counts))
    assert int(dropped) == 0
    grads = jax.grad(lambda x_, *m: jnp.sum(
        held_experts(x_, idx, w, *m, 0, rows)[0]), range(4))(x, *mats)
    assert all(not np.any(np.asarray(g)) for g in grads)


# ---- the model ------------------------------------------------------------

TINY = dict(vocab=64, hidden=32, num_heads=2, kda_head_dim=16, conv_size=4,
            qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16, kv_rank=12,
            dense_width=48, expert_width=24, num_experts=16, experts_held=4,
            expert_offset=4, top_k=4, routed_scaling=2.446, loss_block=32,
            kda_chunk=16,
            layers=(("kda", "mlp"), ("kda", "moe"), ("mla", "moe")))


def tiny_model_and_batch(**over):
    model = get_model("kimi_linear", **{**TINY, **over})
    tokens = np.random.default_rng(0).integers(0, 64, (2, 41))
    x, y = jnp.asarray(tokens[:, :-1]), jnp.asarray(tokens[:, 1:])
    variables = jax.jit(lambda: model.init(jax.random.PRNGKey(1), x))()
    return model, variables, x, y


def reference_sizes():
    return {**{k: TINY[k] for k in ("layers", "num_heads", "qk_nope_dim",
                                    "qk_rope_dim", "kv_rank", "expert_offset",
                                    "top_k", "routed_scaling")}, "eps": 1e-5}


def test_model_loss_and_gradient_equal_the_plain_reference():
    model, variables, x, y = tiny_model_and_batch()
    ours = lambda p: model.apply({"params": p}, x, y, method="loss_and_aux")[0]
    theirs = lambda p: plain.loss(p, x, y, reference_sizes(), NX)
    params = variables["params"]
    np.testing.assert_allclose(ours(params), theirs(params), rtol=2e-6)
    got, want = jax.grad(ours)(params), jax.grad(theirs)(params)
    norm = np.sqrt(sum(float(jnp.sum(w * w)) for w in jax.tree.leaves(want)))
    off = np.sqrt(sum(float(jnp.sum((g - w) ** 2)) for g, w in
                      zip(jax.tree.leaves(got), jax.tree.leaves(want))))
    assert off / norm < 2e-5


def test_whole_logits_agree_with_the_blocked_loss_and_the_reference():
    model, variables, x, y = tiny_model_and_batch()
    logits = model.apply(variables, x)
    np.testing.assert_allclose(
        logits, plain.logits(variables["params"], x, reference_sizes(), NX),
        atol=2e-5)
    loss, aux = model.apply(variables, x, y, method="loss_and_aux")
    logz = jax.nn.logsumexp(logits, -1)
    picked = jnp.take_along_axis(logits, y[..., None], -1)[..., 0]
    np.testing.assert_allclose(loss, jnp.mean(logz - picked), rtol=1e-6)
    assert set(aux["counters"]) == {
        "moe/assignments_min", "moe/assignments_mean", "moe/assignments_max",
        "moe/dropped"}
    assert float(aux["counters"]["moe/dropped"]) == 0.0
    # 2 expert layers x 4 held of 16 experts: 80 tokens x top-4 a layer,
    # a quarter of them here on average
    counters = {k: float(v) for k, v in aux["counters"].items()}
    assert (counters["moe/assignments_min"] <= counters["moe/assignments_mean"]
            <= counters["moe/assignments_max"] <= 80)
    assert 0 < counters["moe/assignments_mean"] * 8 <= 2 * 80 * 4


def test_a_model_without_expert_layers_counts_nothing():
    model, variables, x, y = tiny_model_and_batch(
        layers=(("kda", "mlp"), ("mla", "mlp")))
    _, aux = model.apply(variables, x, y, method="loss_and_aux")
    assert set(aux) == {"accuracy"}


def test_rematerialisation_changes_no_number():
    grads = []
    for remat in (True, False):
        model, variables, x, y = tiny_model_and_batch(remat=remat)
        grads.append(jax.grad(lambda p: model.apply(
            {"params": p}, x, y, method="loss_and_aux")[0])(
                variables["params"]))
    for a, b in zip(*map(jax.tree.leaves, grads)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_trainer_takes_the_loss_from_the_model_and_counts():
    """`Trainer.fit` on the decoder: per-token labels through the loader,
    the model's loss in the step, its counters in `LoopStats`."""
    import optax
    from geomx_tpu import GeoConfig, HiPSTopology
    from geomx_tpu.sync import get_sync_algorithm
    from geomx_tpu.train import Trainer
    model, _, _, _ = tiny_model_and_batch()
    tokens = np.random.default_rng(1).integers(0, 64, (8, 41)).astype(np.int32)
    x, y = tokens[:, :-1], tokens[:, 1:]
    cfg = GeoConfig(num_parties=1, workers_per_party=1, sync_mode="fsa",
                    compression="none")
    topo = HiPSTopology(1, 1)
    trainer = Trainer(model, topo, optax.adam(1e-2),
                      sync=get_sync_algorithm(cfg), config=cfg)
    state = trainer.init_state(jax.random.PRNGKey(0), x[:2])
    loader = trainer.make_loader(x, y, 2, seed=0)
    state, records = trainer.fit(state, loader, epochs=3, log_every=1,
                                 log_fn=lambda _line: None)
    losses = [r["loss"] for r in records if "loss" in r]
    assert len(losses) == 12 and losses[-1] < losses[0]
    counters = trainer.loop_stats.as_dict()["counters"]
    assert counters["moe/dropped"] == {"count": 12, "total": 0.0,
                                       "last": 0.0, "max": 0.0}
    # 2 x 40 tokens x top-4 of 16, 4 held: 80 assignments a layer on average
    assert counters["moe/assignments_mean"]["count"] == 12
    assert 5.0 < counters["moe/assignments_mean"]["last"] < 40.0


def test_init_state_lets_go_of_what_it_replicates():
    from geomx_tpu import HiPSTopology
    from geomx_tpu.train.state import replicate_consuming, replicate_tree
    topo = HiPSTopology(1, 1)
    mesh = topo.build_mesh()
    trees = [{"a": jnp.arange(4.0), "b": {"c": jnp.ones((2, 3))}},
             (jnp.zeros(()),)]
    want = [replicate_tree(t, topo, mesh) for t in trees]
    got = replicate_consuming(trees, topo, mesh)
    assert trees == []
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape and a.sharding == b.sharding
        np.testing.assert_array_equal(a, b)
    assert jax.tree.structure(got) == jax.tree.structure(want)


def test_the_new_scopes_are_pairs_of_the_vocabulary():
    from geomx_tpu.telemetry.layers import classify_op_name, layer_of
    for scope, layer in [("kda/proj", "step program"), ("kda/scan", "kernels"),
                         ("mla/proj", "step program"),
                         ("mla/attention", "kernels"),
                         ("moe/route", "step program"),
                         ("moe/experts", "step program"),
                         ("moe/shared", "step program"),
                         ("lm/loss", "step program")]:
        assert layer_of(scope) == layer
    got = classify_op_name(
        "jit(_device_step)/step/forward_backward/transpose(jvp(Kimi))/"
        "checkpoint/layer2/mixer/kda/scan/while/body/dot_general")
    assert got.scope == "step/forward_backward/kda/scan"
    assert got.layer == "kernels" and got.direction == "backward"
    # a flax module's own name never reads as one of them
    plain_name = classify_op_name(
        "jit(_device_step)/step/forward_backward/jvp(Kimi)/layer2/mixer/"
        "out_norm/mul")
    assert plain_name.scope == "step/forward_backward"


def test_the_compiled_step_names_the_decoders_layers():
    """Every new scope reaches the compiled program's instruction names,
    among them the bodies of the `while`s (the scan, the experts' loop)."""
    from geomx_tpu.telemetry.layers import op_layers
    model, variables, x, y = tiny_model_and_batch()
    from geomx_tpu.utils.profiler import profile_scope

    def step(p):
        with profile_scope("step/forward_backward"):
            return jax.grad(lambda p_: model.apply(
                {"params": p_}, x, y, method="loss_and_aux")[0])(p)

    text = jax.jit(step).lower(variables["params"]).compile().as_text()
    scopes = {entry.scope for entry in op_layers(text).values()
              if entry.scope}
    for needle in ("kda/proj", "kda/scan", "mla/proj", "mla/attention",
                   "moe/route", "moe/experts", "moe/shared", "lm/loss"):
        assert any(needle in s for s in scopes), (needle, sorted(scopes))
