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

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_checks as checks
from benchmark.references import mellum as plain
from benchmark.references.kimi_linear import rms_norm
from geomx_tpu.models import decoder, get_model, mellum
from geomx_tpu.ops import dispatch
from geomx_tpu.ops import gqa_elementwise as ge

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


NX = checks.NX
FAMILY = checks.Family(
    "mellum", {**TINY, "yarn": ge.Yarn(**TINY_YARN), **PROGRAM}, plain,
    {**TINY, "yarn": TINY_YARN, "eps": 1e-6})


@pytest.fixture(scope="module")
def built():
    return checks.Built(FAMILY)


def test_the_shared_pieces_have_one_copy():
    from geomx_tpu.models import afmoe
    assert issubclass(mellum.MellumLM, decoder.DecoderLM)
    cfg = mellum.MellumConfig(**TINY, yarn=ge.Yarn(**TINY_YARN))
    for kind in ("window", "global"):
        assert type(cfg.make_mixer(kind, jnp.float32)) is afmoe.GQAMixer
    assert (cfg.post_norms, cfg.embedding_scale, cfg.shared_experts) == (
        False, 1.0, 0)


def test_model_loss_and_every_gradient_leaf_equal_the_plain_reference(built):
    got, want = checks.loss_equals_the_reference(built)
    flat = jax.tree_util.tree_flatten_with_path(want)[0]
    for (path, w), g in zip(flat, jax.tree.leaves(got)):
        scale = float(jnp.max(jnp.abs(w)))
        assert scale > 0, path          # every leaf takes part
        np.testing.assert_allclose(g, w, atol=3e-5 * scale, err_msg=str(path))
    # a block's two norms, the q/k norms and the final one
    names = [p[-1].key for p, _ in flat]
    assert names.count("scale") == 3 * (2 + 2) + 1


def test_whole_logits_agree_with_the_blocked_loss_and_the_reference(built):
    checks.whole_logits_agree(built, atol=3e-5)


def test_the_kernels_give_what_the_dense_fall_back_gives(built):
    checks.kernels_give_the_dense_fall_back(built)


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

    def value_and_pull(form):
        def run(*a):
            out, pull = jax.vjp(form, *a)
            return out, pull((gq, gk))
        return jax.jit(run)(q, k, q_scale, k_scale)

    want, pulled_want = value_and_pull(lambda *a: plain_chain(*a, yarn))
    for form in (kernel, lambda *a: ge.norm_rotary_ref(*a, 1e-6, rope)):
        got, pulled = value_and_pull(form)
        for a, b in zip(got, want):
            np.testing.assert_allclose(a, b, atol=2e-5)
        for a, b in zip(pulled, pulled_want):
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
        params = jax.jit(mixer.init)(jax.random.PRNGKey(1), x)
        assert "gate_kernel" not in params["params"]
        apply = jax.jit(mixer.apply)
        last[kind] = [apply(params, v)[0, -1] for v in (x, swapped)]
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


def test_an_expert_layer_with_no_shared_expert_has_no_shared_kernels(built):
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
    text = built.lowered.as_text(debug_info=True)
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
    reference = jax.jit(lambda p, lo: plain.moe(NX, x, p, lo, 4),
                        static_argnums=1)
    arrived = checks.expert_shares_add_up(
        lambda lo: decoder.HeldExpertsLayer(
            16, 4, lo, 4, 24, 1.0, shared_experts=0, rows=8, pool=32,
            scoring="softmax"), reference, whole, x, reference(whole, 0))
    assert arrived == 2 * 20 * 4        # every assignment lands on one share


def test_rematerialisation_changes_no_number(built):
    checks.rematerialisation_changes_no_number(built, rtol=1e-4, atol=1e-5)


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
    """`get_model("mellum")` through `Trainer.fit` and FSA's dense tier."""
    counters = checks.trainer_fits(FAMILY, 1e-3, epochs=2)
    assert 0.0 < counters["moe/pool_fill"]["max"] <= 1.0
