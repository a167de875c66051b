"""The Mellum decoder (`models/mellum.py`: `afmoe.GQAMixer` with rotary on
every layer under a table per layer kind and no gate, a softmax router,
an expert layer with no shared expert) against the plain reference's
equations (`benchmark/references/mellum.py`), at tiny sizes on seeded
weights: the model's loss and every gradient leaf, YaRN's table at the
published numbers, the streamed norm + rotary pass under a YaRN table,
the router, the expert layer without a shared expert and its four shares,
and the other decoders' parameter trees."""
import hashlib
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.references import mellum as plain  # noqa: E402
from benchmark.references.kimi_linear import rms_norm  # noqa: E402
from benchmark.references.numerics import Numerics  # noqa: E402
from geomx_tpu.models import decoder, get_model, mellum  # noqa: E402
from geomx_tpu.ops import dispatch  # noqa: E402
from geomx_tpu.ops import gqa_elementwise as ge  # noqa: E402

NX = Numerics("float32")

# `rope_parameters.full_attention` of Mellum2-12B-A2.5B's config.json
PUBLISHED = dict(theta=500000.0, factor=16.0, original=8192, beta_fast=32.0,
                 beta_slow=1.0, attention_factor=1.2772588722239782)
# 8 query heads on 2 key/value heads of 16; a band of 12 keys over 40; a
# ramp over pairs 0..2 of a head's 8
TINY_YARN = dict(theta=10000.0, factor=4.0, original=16, beta_fast=2.0,
                 beta_slow=0.5, attention_factor=0.1 * math.log(4.0) + 1.0)
TINY = dict(vocab=64, hidden=32, num_heads=8, num_kv_heads=2, head_dim=16,
            window=12, rope_theta=10000.0, expert_width=24, num_experts=16,
            experts_held=4, expert_offset=4, top_k=4,
            layers=(("window", "moe"), ("global", "moe"), ("window", "moe")))
PROGRAM = dict(loss_block=32, expert_rows=8, expert_pool=64)


def tiny_model_and_batch(**over):
    model = get_model("mellum", **{**TINY, "yarn": ge.Yarn(**TINY_YARN),
                                   **PROGRAM, **over})
    tokens = np.random.default_rng(0).integers(0, 64, (2, 41))
    x, y = jnp.asarray(tokens[:, :-1]), jnp.asarray(tokens[:, 1:])
    variables = jax.jit(lambda: model.init(jax.random.PRNGKey(1), x))()
    # norms' scales off one, so that a norm left out or misplaced shows
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a + 0.1 * jax.random.normal(
            jax.random.PRNGKey(len(path)), a.shape)
        if path[-1].key == "scale" else a, variables["params"])
    return model, params, x, y


def reference_sizes(**over):
    return {**TINY, "yarn": TINY_YARN, "eps": 1e-6, **over}


def loss_of(model, x, y):
    return lambda p: model.apply({"params": p}, x, y,
                                 method="loss_and_aux")[0]


def test_the_shared_pieces_have_one_copy():
    from geomx_tpu.models import afmoe
    assert issubclass(mellum.MellumLM, decoder.DecoderLM)
    cfg = mellum.MellumConfig(**TINY, yarn=ge.Yarn(**TINY_YARN))
    for kind in ("window", "global"):
        assert type(cfg.make_mixer(kind, jnp.float32)) is afmoe.GQAMixer
    assert (cfg.post_norms, cfg.embedding_scale, cfg.shared_experts) == (
        False, 1.0, 0)


def test_model_loss_and_every_gradient_leaf_equal_the_plain_reference():
    model, params, x, y = tiny_model_and_batch()
    ours = loss_of(model, x, y)
    theirs = lambda p: plain.loss(p, x, y, reference_sizes(), NX)
    np.testing.assert_allclose(ours(params), theirs(params), rtol=2e-6)
    got, want = jax.grad(ours)(params), jax.grad(theirs)(params)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    flat = jax.tree_util.tree_flatten_with_path(want)[0]
    for (path, w), g in zip(flat, jax.tree.leaves(got)):
        scale = float(jnp.max(jnp.abs(w)))
        assert scale > 0, path          # every leaf takes part
        np.testing.assert_allclose(g, w, atol=3e-5 * scale, err_msg=str(path))
    # a block's two norms, the q/k norms and the final one
    names = [p[-1].key for p, _ in flat]
    assert names.count("scale") == 3 * (2 + 2) + 1


def test_whole_logits_agree_with_the_blocked_loss_and_the_reference():
    model, params, x, y = tiny_model_and_batch()
    logits = model.apply({"params": params}, x)
    np.testing.assert_allclose(
        logits, plain.logits(params, x, reference_sizes(), NX), atol=3e-5)
    loss, aux = model.apply({"params": params}, x, y, method="loss_and_aux")
    logz = jax.nn.logsumexp(logits, -1)
    picked = jnp.take_along_axis(logits, y[..., None], -1)[..., 0]
    np.testing.assert_allclose(loss, jnp.mean(logz - picked), rtol=1e-6)
    assert float(aux["counters"]["moe/dropped"]) == 0.0


def test_the_kernels_give_what_the_dense_fall_back_gives():
    model, params, x, y = tiny_model_and_batch()
    ours = loss_of(model, x, y)
    want = jax.value_and_grad(ours)(params)
    with dispatch.kernels("interpret"):
        got = jax.value_and_grad(ours)(params)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    for a, b in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


def test_yarn_table_at_the_published_numbers_is_the_references_own():
    """18.08 and 34.98 round outwards to 18 and 35; below the ramp a pair
    keeps its frequency, above it the frequency is a sixteenth; the
    factor on cos and sin is 0.1 ln 16 + 1."""
    yarn = ge.Yarn(**PUBLISHED)
    assert ge.yarn_correction_range(128, yarn) == (18, 35)
    assert plain.yarn_range(128, PUBLISHED) == (18, 35)
    inverse, factor = ge.rotary_frequencies(128, yarn)
    want = plain.yarn_frequencies(128, PUBLISHED)
    np.testing.assert_allclose(inverse, want, rtol=2e-6)
    plain_rotary = 500000.0 ** (-2.0 * np.arange(64) / 128)
    np.testing.assert_allclose(want[:19], plain_rotary[:19], rtol=1e-12)
    np.testing.assert_allclose(want[35:], plain_rotary[35:] / 16, rtol=1e-12)
    assert np.all(np.diff(want) < 0)
    assert factor == PUBLISHED["attention_factor"] == pytest.approx(
        0.1 * math.log(16.0) + 1.0, rel=1e-15)
    # the default table is what it was: theta's own frequencies, factor 1
    inverse, one = ge.rotary_frequencies(128, 500000.0)
    np.testing.assert_allclose(inverse, plain_rotary, rtol=2e-6)
    assert one == 1.0
    cos, sin = ge.rotary_tables(300, 128, yarn)
    angle = np.arange(300)[:, None] * want[None, :]
    np.testing.assert_allclose(cos[:, :64], factor * np.cos(angle), atol=2e-4)
    np.testing.assert_allclose(sin[:, 64:], factor * np.sin(angle), atol=2e-4)
    np.testing.assert_array_equal(sin[:, :64], -sin[:, 64:])


def plain_chain(q, k, q_scale, k_scale, yarn: dict):
    """The reference's RMSNorm then its rotary under its own table."""
    w = plain.yarn_frequencies(q.shape[-1], yarn)
    f = yarn["attention_factor"]
    return (plain.rotary(rms_norm(q, {"scale": q_scale}, 1e-6), w, f),
            plain.rotary(rms_norm(k, {"scale": k_scale}, 1e-6), w, f))


@pytest.mark.parametrize("length", [128, 80])
def test_norm_rotary_under_a_yarn_table_is_the_plain_chain(length):
    """The kernel pair (interpreted) with YaRN's tables as its operands:
    values and every gradient against the reference's norm then rotary;
    a last tile may be ragged."""
    yarn = dict(PUBLISHED, original=64)     # the ramp inside 80 positions
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    q = jax.random.normal(keys[0], (1, length, 4, 128))
    k = jax.random.normal(keys[1], (1, length, 2, 128))
    q_scale = 1.0 + 0.1 * jax.random.normal(keys[2], (128,))
    k_scale = 1.0 + 0.1 * jax.random.normal(keys[3], (128,))
    gq, gk = (jax.random.normal(keys[4], q.shape),
              jax.random.normal(keys[5], k.shape))
    rope = ge.Yarn(**yarn)
    assert ge.norm_rotary_plan(q.shape, k.shape, q.dtype) is not None

    def kernel(*a):
        with dispatch.kernels("interpret"):
            return dispatch.gqa_norm_rotary(*a, 1e-6, rope)

    want, pull_want = jax.vjp(lambda *a: plain_chain(*a, yarn), q, k,
                              q_scale, k_scale)
    for form in (kernel, lambda *a: ge.norm_rotary_ref(*a, 1e-6, rope)):
        got, pull = jax.vjp(form, q, k, q_scale, k_scale)
        for a, b in zip(got, want):
            np.testing.assert_allclose(a, b, atol=2e-5)
        for a, b in zip(pull((gq, gk)), pull_want((gq, gk))):
            np.testing.assert_allclose(
                a, b, atol=3e-5 * float(jnp.max(jnp.abs(b))))
    # the kernels did run under the door's interpret mode
    with dispatch.kernels("interpret"):
        text = str(jax.make_jaxpr(lambda *a: dispatch.gqa_norm_rotary(
            *a, 1e-6, rope))(q, k, q_scale, k_scale))
    assert "gqa_norm_rotary_fwd" in text


def test_both_kinds_of_layer_take_positions_from_their_own_table():
    """Swapping two earlier tokens moves the last token's output in a
    window layer and in a global one (Trinity's global layers are blind to
    it); the two kinds turn by different tables."""
    cfg = mellum.MellumConfig(**{**TINY, "yarn": ge.Yarn(**TINY_YARN),
                                 "window": 64})
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 10, 32))
    swapped = x.at[0, 2].set(x[0, 5]).at[0, 5].set(x[0, 2])
    last = {}
    for kind in ("window", "global"):
        mixer = cfg.make_mixer(kind, jnp.float32)
        params = mixer.init(jax.random.PRNGKey(1), x)
        assert "gate_kernel" not in params["params"]
        last[kind] = [mixer.apply(params, v)[0, -1] for v in (x, swapped)]
        assert float(jnp.max(jnp.abs(last[kind][0] - last[kind][1]))) > 1e-3
    assert float(jnp.max(jnp.abs(last["window"][0] - last["global"][0]))) \
        > 1e-3
    assert cfg.make_mixer("window", jnp.float32).rope == 10000.0
    assert cfg.make_mixer("global", jnp.float32).rope == ge.Yarn(**TINY_YARN)


def test_softmax_route_is_the_references_and_weights_sum_to_one():
    keys = jax.random.split(jax.random.PRNGKey(2), 2)
    x = jax.random.normal(keys[0], (50, 32))
    router = jax.random.normal(keys[1], (32, 16)) * 32 ** -0.5
    idx, weights = decoder.route(x, router, jnp.zeros((16,)), 4, 1.0,
                                 "softmax")
    np.testing.assert_allclose(jnp.sum(weights, -1), 1.0, rtol=1e-6)
    want = plain.routing(NX, x, router, 4)
    rows = jnp.arange(50)[:, None]
    got = jnp.zeros((50, 16)).at[rows, idx].set(weights)
    np.testing.assert_allclose(got, want, atol=1e-6)
    # probabilities over ALL the experts: the picked are the largest of a
    # softmax, and their weights its values renormalised
    p = jax.nn.softmax(x @ router, -1)
    np.testing.assert_array_equal(jnp.sort(idx, -1),
                                  jnp.sort(jax.lax.top_k(p, 4)[1], -1))
    # the sigmoid router is untouched by the new argument's default
    a = decoder.route(x, router, jnp.zeros((16,)), 4, 2.5)
    b = decoder.route(x, router, jnp.zeros((16,)), 4, 2.5, "sigmoid")
    np.testing.assert_array_equal(a[1], b[1])
    with pytest.raises(KeyError):
        decoder.route(x, router, jnp.zeros((16,)), 4, 1.0, "tanh")


def whole_layer(hidden=32, width=24, experts=16, seed=3):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    fan = lambda k, shape: jax.random.normal(k, shape) * shape[-2] ** -0.5
    return {"router_kernel": fan(ks[0], (hidden, experts)),
            "experts_gate_kernel": fan(ks[1], (experts, hidden, width)),
            "experts_up_kernel": fan(ks[2], (experts, hidden, width)),
            "experts_down_kernel": fan(ks[3], (experts, width, hidden))}, \
        jax.random.normal(ks[4], (2, 20, hidden))


def test_an_expert_layer_with_no_shared_expert_has_no_shared_kernels():
    whole, x = whole_layer()
    layer = decoder.HeldExpertsLayer(16, 16, 0, 4, 24, 1.0, shared_experts=0,
                                     rows=8, pool=64, scoring="softmax")
    params = jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)["params"]
    assert sorted(params) == sorted(whole)
    text = jax.jit(jax.grad(lambda p: jnp.sum(layer.apply(
        {"params": p}, x)[0]))).lower(whole).as_text(debug_info=True)
    assert "moe/route/" in text and "moe/experts/" in text
    assert "moe/shared" not in text
    # one shared expert, as every other decoder has: kernels and scope
    shared = decoder.HeldExpertsLayer(16, 16, 0, 4, 24, 1.0, rows=8, pool=64)
    names = jax.eval_shape(shared.init, jax.random.PRNGKey(0), x)["params"]
    assert {"shared_gate_kernel", "shared_up_kernel",
            "shared_down_kernel"} <= set(names)
    # and the whole model's compiled step opens every scope but that one
    model, params, x, y = tiny_model_and_batch()
    text = jax.jit(jax.grad(loss_of(model, x, y))).lower(params).as_text(
        debug_info=True)
    for scope in ("gqa/proj", "gqa/window", "gqa/global", "attn/core",
                  "moe/route", "moe/experts", "moe/plan", "moe/dispatch",
                  "lm/loss"):
        assert scope + "/" in text, scope
    assert "moe/shared" not in text and "moe/latent" not in text


def test_the_four_expert_shares_add_up_to_the_uncut_reference():
    """16 experts cut into 4 shares of 4 (offsets 0, 4, 8, 12; the cell's
    0, 16, 32, 48 of 64): the parts the shares give, with nothing that
    every chip computes alike, are what the reference gives with all 16
    held."""
    whole, x = whole_layer()
    uncut = plain.moe(NX, x, whole, 0, 4)
    total, arrived = 0.0, 0
    for share in range(4):
        lo = 4 * share
        part = {k: (v[lo:lo + 4] if k.startswith("experts_") else v)
                for k, v in whole.items()}
        layer = decoder.HeldExpertsLayer(16, 4, lo, 4, 24, 1.0,
                                         shared_experts=0, rows=8, pool=32,
                                         scoring="softmax")
        y, counts, dropped = layer.apply({"params": part}, x)
        np.testing.assert_allclose(y, plain.moe(NX, x, part, lo, 4),
                                   atol=2e-5)
        total = total + y
        arrived += int(jnp.sum(counts))
        assert int(dropped) == 0
    np.testing.assert_allclose(total, uncut, atol=5e-5)
    assert arrived == 2 * 20 * 4        # every assignment lands on one share


def test_rematerialisation_changes_no_number():
    grads = []
    for remat in (True, False):
        model, params, x, y = tiny_model_and_batch(remat=remat)
        grads.append(jax.grad(loss_of(model, x, y))(params))
    for a, b in zip(*map(jax.tree.leaves, grads)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


# tiny configurations of the other decoders and the digest of their
# parameter trees (paths and shapes, sorted) as the parent of PR 40 gave it
OTHERS = {
    "afmoe": (dict(
        vocab=64, hidden=32, num_heads=8, num_kv_heads=2, head_dim=16,
        window=12, rope_theta=10000.0, dense_width=48, expert_width=24,
        num_experts=16, experts_held=4, expert_offset=4, top_k=4,
        routed_scaling=2.826, embedding_scale=math.sqrt(32),
        layers=(("window", "mlp"), ("window", "moe"), ("global", "moe"))),
        53, "9115a7aa450498db"),
    "kimi_linear": (dict(
        vocab=64, hidden=32, num_heads=2, kda_head_dim=16, conv_size=4,
        qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16, kv_rank=12,
        dense_width=48, expert_width=24, num_experts=16, experts_held=4,
        expert_offset=4, top_k=4, routed_scaling=2.446,
        layers=(("kda", "mlp"), ("kda", "moe"), ("mla", "moe"))),
        61, "e94994c6d0db7f49"),
    "nemotron_h": (dict(
        vocab=64, hidden=32, mamba_heads=2, mamba_head_dim=8, mamba_groups=1,
        state_size=16, conv_size=4, num_heads=2, num_kv_heads=1, head_dim=16,
        expert_width=24, shared_width=40, latent=16, num_experts=16,
        experts_held=4, expert_offset=4, top_k=6, routed_scaling=5.0,
        layers=(("mamba", None), (None, "moe"), ("attention", None))),
        25, "105484cddcbede51"),
}


@pytest.mark.parametrize("name", sorted(OTHERS))
def test_the_other_decoders_parameter_trees_are_unchanged(name):
    sizes, leaves, digest = OTHERS[name]
    model = get_model(name, **sizes)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), np.zeros((1, 8), np.int32)))["params"]
    lines = sorted(
        "/".join(k.key for k in path) + " " + "x".join(map(str, leaf.shape))
        for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0])
    assert len(lines) == leaves
    assert hashlib.sha256("\n".join(lines).encode()).hexdigest()[:16] == \
        digest, lines
    if name == "afmoe":     # gate, post-norms and shared expert stay
        for needle in ("layer2/mixer/core/gate_kernel", "layer2/mixer/post_norm",
                       "layer2/ffn/core/shared_up_kernel"):
            assert any(line.startswith(needle) for line in lines), needle


def test_trainer_takes_the_loss_from_the_model_and_counts():
    """`get_model("mellum")` through `Trainer.fit` and FSA's dense tier
    with no branch on its name."""
    import optax
    from geomx_tpu import GeoConfig, HiPSTopology
    from geomx_tpu.sync import get_sync_algorithm
    from geomx_tpu.train import Trainer
    cfg = GeoConfig(num_parties=1, workers_per_party=1, sync_mode="fsa",
                    compression="none")
    model = get_model("mellum", **{**TINY, "yarn": ge.Yarn(**TINY_YARN),
                                   **PROGRAM})
    trainer = Trainer(model, HiPSTopology(1, 1), optax.adam(1e-3),
                      sync=get_sync_algorithm(cfg), config=cfg)
    tokens = np.random.default_rng(1).integers(0, 64, (8, 41)).astype(
        np.int32)
    x, y = tokens[:, :-1], tokens[:, 1:]
    state = trainer.init_state(jax.random.PRNGKey(0), x[:2])
    state, records = trainer.fit(state, trainer.make_loader(x, y, 2),
                                 epochs=2, log_every=1,
                                 log_fn=lambda _line: None)
    losses = [r["loss"] for r in records if "loss" in r]
    assert len(losses) == 8 and losses[-1] < losses[0]
    counters = trainer.loop_stats.as_dict()["counters"]
    assert counters["moe/dropped"]["total"] == 0.0
    assert counters["moe/assignments_mean"]["count"] == 8
    assert 0.0 < counters["moe/pool_fill"]["max"] <= 1.0
