"""The decoder against the plain reference's equations
(`benchmark/references/kimi_linear.py`: whole softmax attention, whole
logits), at tiny sizes on seeded weights: the blocked loss, the model's
loss and gradient, the loss a model brings to `Trainer`.  Its two ops
(chunked KDA, the held experts) are in `test_kimi_ops.py`."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_checks as checks
from benchmark.references import kimi_linear as plain
from geomx_tpu.models import kimi_linear as kl


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
    (got, got_grads), (want, want_grads) = [
        jax.jit(jax.value_and_grad(f, (0, 1)))(h, head)
        for f in (blocked, whole)]
    np.testing.assert_allclose(got, want, rtol=1e-6)
    for got, want in zip(got_grads, want_grads):
        np.testing.assert_allclose(got, want, atol=1e-5)
    hits = kl.blocked_cross_entropy(h, head, labels, 16)[1]
    assert float(hits) == float(jnp.sum(jnp.argmax(h @ head, -1) == labels))


# ---- the model ------------------------------------------------------------

TINY = dict(vocab=64, hidden=32, num_heads=2, kda_head_dim=16, conv_size=4,
            qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16, kv_rank=12,
            dense_width=48, expert_width=24, num_experts=16, experts_held=4,
            expert_offset=4, top_k=4, routed_scaling=2.446, loss_block=32,
            kda_chunk=16,
            layers=(("kda", "mlp"), ("kda", "moe"), ("mla", "moe")))


FAMILY = checks.Family("kimi_linear", TINY, plain, {
    **{k: TINY[k] for k in ("layers", "num_heads", "qk_nope_dim",
                            "qk_rope_dim", "kv_rank", "expert_offset",
                            "top_k", "routed_scaling")}, "eps": 1e-5})


@pytest.fixture(scope="module")
def built():
    return checks.Built(FAMILY)


def test_model_loss_and_gradient_equal_the_plain_reference(built):
    got, want = checks.loss_equals_the_reference(built)
    assert checks.relative_distance(got, want) < 2e-5


def test_whole_logits_agree_with_the_blocked_loss_and_the_reference(built):
    counters = checks.whole_logits_agree(built, atol=2e-5)
    assert set(counters) == {
        "moe/assignments_min", "moe/assignments_mean", "moe/assignments_max",
        "moe/dropped", "moe/pool_fill"}
    # 2 expert layers x 4 held of 16 experts: 80 tokens x top-4 a layer,
    # a quarter of them here on average
    assert (counters["moe/assignments_min"] <= counters["moe/assignments_mean"]
            <= counters["moe/assignments_max"] <= 80)
    assert 0 < counters["moe/assignments_mean"] * 8 <= 2 * 80 * 4
    # rows moved over places walked: each layer's first pool (2 x 4 held x
    # the tile's rows) holds what arrived
    pools = 2 * 2 * 4 * built.model.cfg.expert_rows
    np.testing.assert_allclose(
        counters["moe/pool_fill"],
        counters["moe/assignments_mean"] * 8 / pools, rtol=1e-6)


def test_a_model_without_expert_layers_counts_nothing():
    bare = checks.Built(FAMILY, layers=(("kda", "mlp"), ("mla", "mlp")))
    _, aux = jax.jit(lambda p: bare.model.apply(
        {"params": p}, bare.x, bare.y, method="loss_and_aux"))(bare.params)
    assert set(aux) == {"accuracy"}


def test_rematerialisation_changes_no_number(built):
    checks.rematerialisation_changes_no_number(built, rtol=1e-4, atol=1e-5)


def test_trainer_takes_the_loss_from_the_model_and_counts():
    counters = checks.trainer_fits(FAMILY, 1e-2, epochs=3, seed=0)
    assert counters["moe/dropped"] == {"count": 12, "total": 0.0,
                                       "last": 0.0, "max": 0.0}
    # 2 x 40 tokens x top-4 of 16, 4 held: 80 assignments a layer on average
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
                         ("moe/dispatch", "step program"),
                         ("moe/plan", "step program"),
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


def test_the_compiled_step_names_the_decoders_layers(built):
    """Every new scope reaches the compiled program's instruction names,
    among them the bodies of the `while`s (the scan, the experts' loop)."""
    scopes = built.scopes()
    for needle in ("kda/proj", "kda/scan", "mla/proj", "mla/attention",
                   "moe/route", "moe/experts", "moe/dispatch", "moe/plan",
                   "moe/shared", "lm/loss"):
        assert any(needle in s for s in scopes), (needle, sorted(scopes))
