"""The GLM-4-MoE-Lite decoder (`models/glm4_moe_lite.py`:
`decoder.LatentMixer` with a low-rank query and decoupled rotary, a
sigmoid router with a shared expert, `decoder.MTPModule` behind the last
block) against the plain reference's equations
(`benchmark/references/glm4_moe_lite.py`), at tiny sizes on seeded
weights: the total loss, each of its two parts and every gradient leaf;
the one latent mixer against the mixer Kimi had; rotary on the shared key
part; the module's last position; a chain of two modules; a model with no
module; the eight expert shares; and the other decoders' parameter
trees."""
import hashlib

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_checks as checks
import test_mellum
from benchmark.references import glm4_moe_lite as plain
from benchmark.references import kimi_linear as plain_kimi
from geomx_tpu.models import decoder, get_model, glm4_moe_lite
from geomx_tpu.ops.flash_attention import fused_attention
from geomx_tpu.ops.gqa_elementwise import rotary_tables

# 2 heads of [16 nope | 8 rope] keys and 24-wide values (v unlike q/k, as
# 256 is unlike Kimi's 128), a 12-wide query latent; a dense lead, two
# expert layers with 4 of 16 experts held, one module
TINY = dict(vocab=64, hidden=32, num_heads=2, q_rank=12, kv_rank=12,
            qk_nope_dim=16, qk_rope_dim=8, v_head_dim=24, rope_theta=10000.0,
            dense_width=48, expert_width=24, num_experts=16, experts_held=4,
            expert_offset=4, top_k=4, routed_scaling=1.8,
            layers=(("mla", "mlp"), ("mla", "moe"), ("mla", "moe")))
PROGRAM = dict(loss_block=32, expert_rows=8, expert_pool=64)
REFERENCE = {**TINY, "mtp_depth": 1, "mtp_weight": 0.3,
             "mtp_block": ("mla", "moe"), "eps": 1e-5}

NX = checks.NX
FAMILY = checks.Family("glm4_moe_lite", {**TINY, **PROGRAM}, plain, REFERENCE)


@pytest.fixture(scope="module")
def built():
    return checks.Built(FAMILY)


def test_the_shared_pieces_have_one_copy():
    from geomx_tpu.models import kimi_linear
    assert issubclass(glm4_moe_lite.Glm4MoeLiteLM, decoder.DecoderLM)
    cfg = glm4_moe_lite.Glm4MoeLiteConfig(**TINY)
    ours = cfg.make_mixer("mla", jnp.float32)
    kimis = kimi_linear.KimiLinearConfig(
        vocab=64, hidden=32, layers=(), num_heads=2, kda_head_dim=16,
        conv_size=4, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16, kv_rank=12,
        dense_width=48, expert_width=24, num_experts=16, experts_held=4,
        expert_offset=4, top_k=4, routed_scaling=2.446).make_mixer(
        "mla", jnp.float32)
    assert type(ours) is type(kimis) is decoder.LatentMixer
    assert (ours.q_rank, ours.rope) == (12, 10000.0)
    assert (kimis.q_rank, kimis.rope) == (None, None)
    assert not hasattr(kimi_linear, "MLAMixer")
    assert (cfg.post_norms, cfg.embedding_scale, cfg.expert_form) == (
        False, 1.0, {})
    assert (cfg.mtp_depth, cfg.mtp_weight, cfg.mtp_block) == (
        1, 0.3, ("mla", "moe"))
    with pytest.raises(ValueError, match="no mixer"):
        cfg.make_mixer("kda", jnp.float32)


def test_model_loss_and_every_gradient_leaf_equal_the_plain_reference(built):
    got, want = checks.loss_equals_the_reference(built)
    flat = jax.tree_util.tree_flatten_with_path(want)[0]
    for (path, w), g in zip(flat, jax.tree.leaves(got)):
        scale = float(jnp.max(jnp.abs(w)))
        assert scale > 0, path          # every leaf takes part
        np.testing.assert_allclose(g, w, atol=3e-5 * scale, err_msg=str(path))
    names = ["/".join(k.key for k in p) for p, _ in flat]
    # embedding and head are leaves of the model alone: the module has none
    assert [n for n in names if "embedding" in n or "head_kernel" in n] == [
        "embedding", "head_kernel"]
    # a block's two norms and the mixer's two latent norms, four blocks;
    # the module's three; the final one
    assert sum(n.endswith("scale") for n in names) == 4 * (2 + 2) + 3 + 1
    assert sorted(n for n in names if n.startswith("mtp1/")
                  and "/block/" not in n) == [
        "mtp1/hidden_norm/scale", "mtp1/join_kernel", "mtp1/out_norm/scale",
        "mtp1/token_norm/scale"]


def test_each_of_the_two_losses_is_the_references(built):
    """`lm/main_loss` and `mtp/loss` beside the total: main + 0.3 x
    module; `accuracy` stays the main head's."""
    (loss, aux), _ = built.ours
    main, further = jax.jit(lambda p: plain.losses(
        p, built.x, built.y, REFERENCE, NX))(built.params)
    counters = aux["counters"]
    np.testing.assert_allclose(counters["lm/main_loss"], main, rtol=2e-6)
    np.testing.assert_allclose(counters["mtp/loss"], further[0], rtol=2e-6)
    np.testing.assert_allclose(loss, main + 0.3 * further[0], rtol=2e-6)
    assert abs(float(further[0]) - float(main)) > 1e-3
    logits = jax.jit(lambda p: built.model.apply({"params": p}, built.x))(
        built.params)
    assert float(aux["accuracy"]) == float(
        jnp.mean(jnp.argmax(logits, -1) == built.y))
    assert 0.0 <= float(counters["mtp/accuracy"]) <= 1.0
    # three expert layers' held experts counted together: the module's too
    assert set(counters) == {
        "lm/main_loss", "mtp/loss", "mtp/accuracy", "moe/assignments_min",
        "moe/assignments_mean", "moe/assignments_max", "moe/dropped",
        "moe/pool_fill"}
    assert 0.0 < float(counters["moe/pool_fill"]) <= 1.0
    # 80 tokens x top 4 a layer over 16 experts, 4 held: 20 each if even
    assert 0 < float(counters["moe/assignments_mean"]) * 12 <= 3 * 80 * 4
    without = checks.Built(checks.Family(
        "glm4_moe_lite", {**TINY, **PROGRAM, "mtp_depth": 0}, plain,
        {**REFERENCE, "mtp_depth": 0}))
    (_, fewer), _ = without.ours
    assert float(fewer["counters"]["moe/assignments_mean"]) != float(
        counters["moe/assignments_mean"])


def test_whole_logits_agree_with_the_main_loss_and_the_reference(built):
    """`__call__` is the main model's head alone (evaluation reads it), and
    the main loss is its cross-entropy."""
    f = built.family
    logits = jax.jit(lambda p: built.model.apply({"params": p}, built.x))(
        built.params)
    want = jax.jit(lambda p: plain.logits(p, built.x, f.reference_sizes, NX))(
        built.params)
    np.testing.assert_allclose(logits, want, atol=3e-5)
    (_, aux), _ = built.ours
    picked = jnp.take_along_axis(logits, built.y[..., None], -1)[..., 0]
    np.testing.assert_allclose(
        aux["counters"]["lm/main_loss"],
        jnp.mean(jax.nn.logsumexp(logits, -1) - picked), rtol=1e-6)
    assert float(aux["counters"]["moe/dropped"]) == 0.0


def test_the_kernels_give_what_the_dense_fall_back_gives(built):
    checks.kernels_give_the_dense_fall_back(built)


def test_rematerialisation_changes_no_number(built):
    checks.rematerialisation_changes_no_number(built, rtol=1e-4, atol=1e-5)


# ---- the one latent mixer -------------------------------------------------

class KimisMixer(nn.Module):
    """`models/kimi_linear.MLAMixer` as the parent of PR 45 had it, kept
    here as the oracle of the move: one W_q, no positions."""
    num_heads: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    kv_rank: int
    eps: float

    @nn.compact
    def __call__(self, x):
        h, hidden = self.num_heads, x.shape[-1]
        b, length, _ = x.shape
        qk = self.nope_dim + self.rope_dim
        mat = lambda name, shape: self.param(name, decoder._fan_in, shape)
        q = jnp.dot(x, mat("q_kernel", (hidden, h * qk)))
        kv = jnp.dot(x, mat("kv_a_kernel",
                            (hidden, self.kv_rank + self.rope_dim)))
        latent = decoder.RMSNorm(self.eps, name="kv_norm")(
            kv[..., :self.kv_rank])
        shared = kv[..., self.kv_rank:]
        kv_b = jnp.dot(latent, mat(
            "kv_b_kernel", (self.kv_rank, h * (self.nope_dim + self.v_dim)))
        ).reshape(b, length, h, self.nope_dim + self.v_dim)
        k = jnp.concatenate(
            [kv_b[..., :self.nope_dim], jnp.broadcast_to(
                shared[:, :, None, :], (b, length, h, self.rope_dim))], -1)
        o = fused_attention(q.reshape(b, length, h, qk), k,
                            kv_b[..., self.nope_dim:], True)
        return jnp.dot(o.reshape(b, length, h * self.v_dim),
                       mat("out_kernel", (h * self.v_dim, hidden)))


def test_with_no_query_rank_and_no_positions_it_is_the_mixer_kimi_had():
    """Same parameter names, shapes and seeded values, the same output and
    gradients in every bit."""
    sizes = (2, 16, 8, 16, 12, 1e-5)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 20, 32))
    old, new = KimisMixer(*sizes), decoder.LatentMixer(*sizes)
    params = jax.jit(old.init)(jax.random.PRNGKey(1), x)
    fresh = jax.jit(new.init)(jax.random.PRNGKey(1), x)
    assert jax.tree.structure(params) == jax.tree.structure(fresh)
    assert sorted(params["params"]) == [
        "kv_a_kernel", "kv_b_kernel", "kv_norm", "out_kernel", "q_kernel"]
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(fresh)):
        np.testing.assert_array_equal(a, b)
    run = lambda m: jax.jit(jax.value_and_grad(
        lambda p, x_: jnp.sum(jnp.sin(m.apply(p, x_))), (0, 1)))(params, x)
    (want, want_grads), (got, got_grads) = run(old), run(new)
    assert float(got) == float(want)
    for a, b in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads)):
        np.testing.assert_array_equal(a, b)
    # and it is the plain reference's latent attention, as it was
    plain_out = plain_kimi.mla(NX, x, params["params"], 2, 16, 8, 12, 1e-5)
    np.testing.assert_allclose(new.apply(params, x), plain_out, atol=2e-5)


def test_a_query_rank_and_positions_are_the_references_attention():
    mixer = glm4_moe_lite.Glm4MoeLiteConfig(**TINY).make_mixer(
        "mla", jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 20, 32))
    params = jax.jit(mixer.init)(jax.random.PRNGKey(1), x)
    assert sorted(params["params"]) == [
        "kv_a_kernel", "kv_b_kernel", "kv_norm", "out_kernel", "q_a_kernel",
        "q_b_kernel", "q_norm"]
    shapes = {k: v.shape for k, v in params["params"].items() if hasattr(
        v, "shape")}
    assert shapes == {"kv_a_kernel": (32, 20), "kv_b_kernel": (12, 80),
                      "out_kernel": (48, 32), "q_a_kernel": (32, 12),
                      "q_b_kernel": (12, 48)}
    want = plain.attention(NX, x, params["params"], REFERENCE)
    np.testing.assert_allclose(jax.jit(mixer.apply)(params, x), want,
                               atol=2e-5)
    # positions matter: swapping two earlier tokens moves the last output
    swapped = x.at[:, 2].set(x[:, 5]).at[:, 5].set(x[:, 2])
    last = [jax.jit(mixer.apply)(params, v)[:, -1] for v in (x, swapped)]
    assert float(jnp.max(jnp.abs(last[0] - last[1]))) > 1e-3
    # while Kimi's mixer (no positions) is blind to it
    blind = decoder.LatentMixer(2, 16, 8, 24, 12, 1e-5)
    p = jax.jit(blind.init)(jax.random.PRNGKey(1), x)
    last = [jax.jit(blind.apply)(p, v)[:, -1] for v in (x, swapped)]
    assert float(jnp.max(jnp.abs(last[0] - last[1]))) < 1e-5


def test_rotary_on_the_shared_key_part_is_applied_once():
    """Turning the one shared part and handing it to every head is turning
    every head's copy; the tables are `ops/gqa_elementwise`'s at theta and
    the rope part's width, and the reference's own rotary agrees."""
    cos, sin = rotary_tables(20, 8, 10000.0)
    shared = jax.random.normal(jax.random.PRNGKey(2), (2, 20, 8))
    once = jnp.broadcast_to(decoder.turn(shared, cos, sin)[:, :, None, :],
                            (2, 20, 3, 8))
    per_head = decoder.turn(jnp.broadcast_to(
        shared[:, :, None, :], (2, 20, 3, 8)), cos[:, None], sin[:, None])
    np.testing.assert_array_equal(once, per_head)
    np.testing.assert_allclose(once[:, :, 0], plain.rotary(shared, 10000.0),
                               atol=1e-6)
    w = 10000.0 ** (-2.0 * np.arange(4) / 8)
    angle = np.arange(20)[:, None] * w
    np.testing.assert_allclose(cos[:, :4], np.cos(angle), atol=1e-6)
    np.testing.assert_allclose(cos[:, 4:], np.cos(angle), atol=1e-6)
    # position 0 turns nothing
    np.testing.assert_array_equal(once[:, 0], jnp.broadcast_to(
        shared[:, 0, None, :], (2, 3, 8)))
    # in the compiled mixer the table meets the shared part at [b, L, 8],
    # never at [b, L, heads, 8]: no sine of the keys' per-head shape
    mixer = glm4_moe_lite.Glm4MoeLiteConfig(**TINY).make_mixer(
        "mla", jnp.float32)
    x = jnp.zeros((2, 20, 32))
    params = jax.jit(mixer.init)(jax.random.PRNGKey(1), x)
    jaxpr = jax.make_jaxpr(mixer.apply)(params, x)
    rolls = [e for e in checks.equations(jaxpr.jaxpr)
             if e.primitive.name in ("concatenate", "slice", "roll")
             and e.outvars[0].aval.shape == (2, 20, 8)]
    assert rolls                       # the shared part is turned alone


# ---- the module -----------------------------------------------------------

def test_the_modules_last_position_adds_nothing(built):
    """The module runs on all L positions; the last has no second-next
    token.  What it alone reads there, the last label's embedding, gets no
    gradient from the module's loss (a token id that appears nowhere else),
    while the same label's embedding one position earlier does; and the
    mean is over the L - 1 positions a row that have a label."""
    tokens = np.asarray(jnp.concatenate([built.x[:, :1], built.y], 1))
    tokens = np.where(tokens == 63, 62, tokens)
    tokens[:, -1] = 63              # id 63: the last label and nothing else
    x, y = jnp.asarray(tokens[:, :-1]), jnp.asarray(tokens[:, 1:])

    def module_loss(p):
        return built.model.apply({"params": p}, x, y, method="loss_and_aux")[
            1]["counters"]["mtp/loss"]
    loss, grads = jax.jit(jax.value_and_grad(module_loss))(built.params)
    rows = np.asarray(grads["embedding"])
    assert not rows[63].any()
    assert np.abs(rows[tokens[0, -2]]).max() > 1e-6
    # the head row of id 63 is the label of position L - 2: that one counts
    assert np.abs(np.asarray(grads["head_kernel"])[:, 63]).max() > 1e-6
    _, further = jax.jit(lambda p: plain.losses(
        p, x, y, REFERENCE, NX))(built.params)
    np.testing.assert_allclose(loss, further[0], rtol=2e-6)


def test_a_chain_of_two_modules_is_the_references():
    """Depth 2 reads depth 1's stream (its block's output before its own
    norm) and the token one further on, and is held to the token after
    that over L - 2 positions; the loss is main + 0.3 / 2 x (the two)."""
    two = checks.Built(checks.Family(
        "glm4_moe_lite", {**TINY, **PROGRAM, "mtp_depth": 2}, plain,
        {**REFERENCE, "mtp_depth": 2}))
    assert sorted(k for k in two.params if k.startswith("mtp")) == [
        "mtp1", "mtp2"]
    got, want = checks.loss_equals_the_reference(two)
    for (path, w), g in zip(jax.tree_util.tree_flatten_with_path(want)[0],
                            jax.tree.leaves(got)):
        scale = float(jnp.max(jnp.abs(w)))
        assert scale > 0, path
        np.testing.assert_allclose(g, w, atol=3e-5 * scale, err_msg=str(path))
    main, further = jax.jit(lambda p: plain.losses(
        p, two.x, two.y, two.family.reference_sizes, NX))(two.params)
    (loss, aux), _ = two.ours
    np.testing.assert_allclose(aux["counters"]["mtp/loss"],
                               (further[0] + further[1]) / 2, rtol=2e-6)
    np.testing.assert_allclose(
        loss, main + 0.15 * (further[0] + further[1]), rtol=2e-6)
    assert abs(float(further[0]) - float(further[1])) > 1e-4


def test_with_no_module_tree_loss_and_program_are_the_other_decoders_own():
    """`mtp_depth` 0: no `mtp` parameters, the main loss alone, the
    counters the four decoders have, and no instruction of the step under
    a scope of the module's."""
    none = checks.Built(checks.Family(
        "glm4_moe_lite", {**TINY, **PROGRAM, "mtp_depth": 0}, plain,
        {**REFERENCE, "mtp_depth": 0}))
    assert not [k for k in none.params if k.startswith("mtp")]
    got, want = checks.loss_equals_the_reference(none)
    assert checks.relative_distance(got, want) < 2e-5
    (loss, aux), _ = none.ours
    assert set(aux["counters"]) == {
        "moe/assignments_min", "moe/assignments_mean", "moe/assignments_max",
        "moe/dropped", "moe/pool_fill"}
    text = none.lowered.as_text(debug_info=True)
    assert "mtp/" not in text and "lm/loss/" in text


def test_the_compiled_step_names_the_modules_layers(built):
    from geomx_tpu.telemetry.layers import classify_op_name, layer_of
    assert layer_of("mtp/module") == layer_of("mtp/combine") == "step program"
    scopes = built.scopes()
    for needle in ("mla/proj", "mla/attention", "attn/core", "moe/route",
                   "moe/experts", "moe/dispatch", "moe/plan", "moe/shared",
                   "lm/loss", "mtp/module/mtp/combine",
                   "mtp/module/mla/proj", "mtp/module/mla/attention/attn/core",
                   "mtp/module/moe/experts", "mtp/module/moe/shared",
                   "mtp/module/lm/loss"):
        assert any(needle in s for s in scopes), (needle, sorted(scopes))
    got = classify_op_name(
        "jit(_device_step)/step/forward_backward/transpose(jvp(Glm))/"
        "mtp/module/mtp1/block/mixer/checkpoint/core/mla/proj/dot_general")
    assert got.scope == "step/forward_backward/mtp/module/mla/proj"
    assert got.layer == "step program" and got.direction == "backward"
    # the modules' flax names never read as a scope
    assert classify_op_name(
        "jit(step)/step/forward_backward/jvp(Glm)/mtp1/out_norm/mul"
    ).scope == "step/forward_backward"


# ---- the expert layer and its eight shares --------------------------------

def whole_layer(hidden=32, width=24, experts=16, seed=3):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    fan = lambda k, shape: jax.random.normal(k, shape) * shape[-2] ** -0.5
    return {"router_kernel": fan(ks[0], (hidden, experts)),
            "experts_gate_kernel": fan(ks[1], (experts, hidden, width)),
            "experts_up_kernel": fan(ks[2], (experts, hidden, width)),
            "experts_down_kernel": fan(ks[3], (experts, width, hidden)),
            "shared_gate_kernel": fan(ks[4], (hidden, width)),
            "shared_up_kernel": fan(ks[5], (hidden, width)),
            "shared_down_kernel": fan(ks[6], (width, hidden))}, \
        jax.random.normal(ks[7], (2, 20, hidden))


def test_the_eight_expert_shares_add_up_to_the_uncut_reference():
    """16 experts cut into 8 shares of 2 (offsets 0, 2, ..., 14; the cell's
    0, 8, ..., 56 of 64): the routed parts the shares give, with the shared
    expert counted once, are what the reference gives with all 16 held."""
    whole, x = whole_layer()
    reference = jax.jit(lambda p, lo: plain.moe(NX, x, p, lo, 4, 1.8),
                        static_argnums=1)
    uncut = reference(whole, 0)
    tokens = x.reshape(-1, 32)
    once = plain.swiglu(NX, tokens, whole["shared_gate_kernel"],
                        whole["shared_up_kernel"],
                        whole["shared_down_kernel"]).reshape(x.shape)
    total, arrived = once, 0
    for lo in range(0, 16, 2):
        part = {k: (v[lo:lo + 2] if k.startswith("experts_") else v)
                for k, v in whole.items()}
        layer = decoder.HeldExpertsLayer(16, 2, lo, 4, 24, 1.8, rows=8,
                                         pool=32)
        y, counts, dropped = jax.jit(layer.apply)({"params": part}, x)
        np.testing.assert_allclose(y, reference(part, lo), atol=2e-5)
        total = total + (y - once)
        arrived += int(jnp.sum(counts))
        assert int(dropped) == 0
    np.testing.assert_allclose(total, uncut, atol=5e-5)
    assert arrived == 2 * 20 * 4        # every assignment lands on one share


def test_sigmoid_route_scales_the_renormalised_picks():
    keys = jax.random.split(jax.random.PRNGKey(2), 2)
    x = jax.random.normal(keys[0], (50, 32))
    router = jax.random.normal(keys[1], (32, 16)) * 32 ** -0.5
    idx, weights = decoder.route(x, router, jnp.zeros((16,)), 4, 1.8)
    np.testing.assert_allclose(jnp.sum(weights, -1), 1.8, rtol=1e-6)
    want = plain.routing(NX, x, router, 4, 1.8)
    got = jnp.zeros((50, 16)).at[jnp.arange(50)[:, None], idx].set(weights)
    np.testing.assert_allclose(got, want, atol=1e-6)


# ---- nothing else moved ---------------------------------------------------

# tiny configurations of the four other decoders and the digest of their
# parameter trees (paths and shapes, sorted): the three `test_mellum.py`
# pinned at the parent of PR 40, and Mellum's own
OTHERS = {**test_mellum.OTHERS,
          "mellum": (test_mellum.FAMILY.sizes, 39, None)}


def tree_lines(model):
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), np.zeros((1, 8), np.int32)))["params"]
    return sorted(
        "/".join(k.key for k in path) + " " + "x".join(map(str, leaf.shape))
        for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0])


@pytest.mark.parametrize("name", sorted(OTHERS))
def test_the_other_decoders_parameter_trees_and_losses_are_unchanged(name):
    """No `mtp` leaf, the digests PR 40's test pinned, and `loss_and_aux`
    still one head pass: no counter of the module's."""
    sizes, leaves, digest = OTHERS[name]
    model = get_model(name, **sizes)
    lines = tree_lines(model)
    assert not [line for line in lines if "mtp" in line]
    assert len(lines) == leaves
    if digest:
        assert hashlib.sha256("\n".join(lines).encode()).hexdigest()[:16] \
            == digest, lines
    x = np.zeros((1, 8), np.int32)
    aux = jax.eval_shape(
        lambda: model.apply(model.init(jax.random.PRNGKey(0), x), x, x,
                            method="loss_and_aux"))[1]
    assert set(aux["counters"]) == {
        "moe/assignments_min", "moe/assignments_mean", "moe/assignments_max",
        "moe/dropped", "moe/pool_fill"}


def test_trainer_takes_the_weighted_loss_from_the_model_and_counts():
    """`get_model("glm4_moe_lite")` through `Trainer.fit`, the loader and
    FSA's dense tier as they are: labels [N, L] are the next token, the
    second-next is the model's own shift of them; the modules' counters
    come through `LoopStats` with the experts'."""
    counters = checks.trainer_fits(FAMILY, 1e-3, epochs=2)
    assert 0.0 < counters["moe/pool_fill"]["max"] <= 1.0
    assert counters["mtp/loss"]["count"] == counters["lm/main_loss"][
        "count"] == 8
    assert counters["mtp/loss"]["last"] > 0.0
    assert 0.0 <= counters["mtp/accuracy"]["max"] <= 1.0
