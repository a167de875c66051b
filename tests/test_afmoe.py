"""The AFMoE decoder (`models/afmoe.py`: gated grouped-query attention in
window and global layers, four norms a block, the shared feed-forward
half) against the plain reference's equations
(`benchmark/references/afmoe.py`), at tiny sizes on seeded weights: the
model's loss and gradient, the shares of an expert layer, rotary on window
layers only, rematerialisation, k and v at their own head count, and the
new scopes."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_checks as checks
from benchmark.references import afmoe as plain
from geomx_tpu.models import afmoe
from geomx_tpu.models import decoder, kimi_linear

# 8 query heads on 2 key/value heads of 16; a band of 12 keys over 40
TINY = dict(vocab=64, hidden=32, num_heads=8, num_kv_heads=2, head_dim=16,
            window=12, rope_theta=10000.0, dense_width=48, expert_width=24,
            num_experts=16, experts_held=4, expert_offset=4, top_k=4,
            routed_scaling=2.826, embedding_scale=math.sqrt(32),
            layers=(("window", "mlp"), ("window", "moe"), ("global", "moe")))
PROGRAM = dict(loss_block=32, expert_rows=8, expert_pool=64)
NX = checks.NX
FAMILY = checks.Family("afmoe", {**TINY, **PROGRAM}, plain,
                        {**TINY, "eps": 1e-5})


@pytest.fixture(scope="module")
def built():
    return checks.Built(FAMILY)


def test_the_shared_pieces_have_one_copy():
    for name in ("RMSNorm", "MLP", "swiglu", "route", "HeldExpertsLayer",
                 "blocked_cross_entropy", "MixerBranch", "FFNBranch", "Block"):
        assert getattr(kimi_linear, name) is getattr(decoder, name), name
    assert issubclass(afmoe.AfmoeLM, decoder.DecoderLM)
    assert issubclass(kimi_linear.KimiLinearLM, decoder.DecoderLM)


def test_model_loss_and_gradient_equal_the_plain_reference(built):
    got, want = checks.loss_equals_the_reference(built)
    assert checks.relative_distance(got, want) < 2e-5
    # a block's four norms, the q/k norms and the final one
    names = [p[-1].key for p, _ in
             jax.tree_util.tree_flatten_with_path(built.params)[0]]
    assert names.count("scale") == 3 * (4 + 2) + 1


def test_whole_logits_agree_with_the_blocked_loss_and_the_reference(built):
    counters = checks.whole_logits_agree(built, atol=3e-5)
    assert 0 < counters["moe/assignments_mean"] <= 80


def test_the_kernels_give_what_the_dense_fall_back_gives(built):
    """The same model through the grouped, windowed Pallas kernels
    (interpreted) and through the dense fall-back a CPU takes."""
    checks.kernels_give_the_dense_fall_back(built)


def test_shares_of_an_expert_layer_add_up_to_the_uncut_reference():
    """16 experts cut into 4 shares of 4: the routed parts the shares give
    (the program's layer, shared expert taken off) plus the shared expert
    counted once are what the reference gives with all 16 held."""
    hidden, width, experts = 32, 24, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 8)
    fan = lambda k, shape: jax.random.normal(k, shape) * shape[-2] ** -0.5
    whole = {"router_kernel": fan(ks[0], (hidden, experts)),
             "shared_gate_kernel": fan(ks[1], (hidden, width)),
             "shared_up_kernel": fan(ks[2], (hidden, width)),
             "shared_down_kernel": fan(ks[3], (width, hidden)),
             "experts_gate_kernel": fan(ks[4], (experts, hidden, width)),
             "experts_up_kernel": fan(ks[5], (experts, hidden, width)),
             "experts_down_kernel": fan(ks[6], (experts, width, hidden))}
    x = jax.random.normal(ks[7], (2, 20, hidden))
    reference = jax.jit(lambda p, lo: plain.moe(NX, x, p, lo, 4, 2.826),
                        static_argnums=1)
    shared = jax.jit(lambda p: plain.swiglu(
        NX, x.reshape(-1, hidden), p["shared_gate_kernel"],
        p["shared_up_kernel"], p["shared_down_kernel"]).reshape(x.shape))(
            whole)
    arrived = checks.expert_shares_add_up(
        lambda lo: decoder.HeldExpertsLayer(experts, 4, lo, 4, width, 2.826,
                                            rows=8, pool=32),
        reference, whole, x, reference(whole, 0), shared)
    assert arrived == 2 * 20 * 4        # every assignment lands on one share


def test_rotary_is_on_window_layers_only():
    """A global layer with no positions is blind to where a (key, value)
    pair sits among the earlier ones; a window layer is not (its band
    covers the whole tiny sequence here, so only rotary tells)."""
    mixer = lambda window: afmoe.GQAMixer(
        4, 2, 16, window, None if window is None else 10000.0, 1e-5)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 10, 32))
    swapped = x.at[0, 2].set(x[0, 5]).at[0, 5].set(x[0, 2])
    last = {}
    for name, window in (("global", None), ("window", 64)):
        params = jax.jit(mixer(window).init)(jax.random.PRNGKey(1), x)
        apply = jax.jit(mixer(window).apply)
        last[name] = [apply(params, v)[0, -1] for v in (x, swapped)]
    np.testing.assert_allclose(*last["global"], atol=1e-6)
    assert float(jnp.max(jnp.abs(last["window"][0] - last["window"][1]))) \
        > 1e-3
    # and the program's rotary is the reference's
    q = jax.random.normal(jax.random.PRNGKey(2), (2, 9, 3, 16))
    np.testing.assert_allclose(afmoe.rotary(q, 10000.0),
                               plain.rotary(q, 10000.0), atol=1e-6)
    np.testing.assert_allclose(afmoe.rotary(q, 10000.0)[:, 0], q[:, 0],
                               atol=1e-7)        # position 0 turns nothing


def test_the_band_is_the_configurations_window():
    """Key j is seen by query i iff 0 <= i - j < window: changing a token
    `window` places back moves nothing in a window layer's output there,
    one place nearer does."""
    model = afmoe.GQAMixer(4, 2, 16, 4, 10000.0, 1e-5)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 12, 32))
    params = jax.jit(model.init)(jax.random.PRNGKey(1), x)
    apply = jax.jit(model.apply)
    at = lambda v: apply(params, v)[0, 9]
    np.testing.assert_allclose(at(x.at[0, 5].add(1.0)), at(x), atol=1e-6)
    assert float(jnp.max(jnp.abs(at(x.at[0, 6].add(1.0)) - at(x)))) > 1e-3


def test_rematerialisation_changes_no_number(built):
    checks.rematerialisation_changes_no_number(built, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("mode", ["dense-fall-back", "interpret"])
def test_k_and_v_are_never_widened_to_the_query_heads(built, mode):
    """8 query heads read 2 key/value heads: no equation of the step, in
    any nested program (the per-sequence scan, the rematerialised halves,
    the attention's own forward and backward rules), takes an array laid
    out as k or v are ([1, L, 2, 16]) and gives one four times as large,
    which is what a repeat, a broadcast or a gather to 8 heads would do;
    and dk, dv come back with 2 heads."""
    from geomx_tpu.ops import dispatch
    fn = built.step_of(built.model)
    if mode == "interpret":
        with dispatch.kernels("interpret"):
            jaxpr = jax.make_jaxpr(fn)(built.params)
    else:
        jaxpr = jax.make_jaxpr(fn)(built.params)
    kv_shape, seen = (1, 40, 2, 16), 0
    for eqn in checks.equations(jaxpr.jaxpr):
        ins = [v.aval.shape for v in eqn.invars if hasattr(v, "aval")]
        if kv_shape not in ins:
            continue
        seen += 1
        for out in eqn.outvars:
            assert out.aval.size != 4 * math.prod(kv_shape) or (
                eqn.primitive.name in ("dot_general", "pallas_call")), eqn
    assert seen > 0     # k and v do cross the program in that layout


def test_the_new_scopes_are_pairs_of_the_vocabulary():
    from geomx_tpu.telemetry.layers import classify_op_name, layer_of
    for scope, layer in [("gqa/proj", "step program"),
                         ("gqa/window", "kernels"),
                         ("gqa/global", "kernels")]:
        assert layer_of(scope) == layer
    got = classify_op_name(
        "jit(_device_step)/step/forward_backward/transpose(jvp(AfmoeLM))/"
        "checkpoint/layer2/mixer/gqa/window/attn/core/dot_general")
    assert got.scope == "step/forward_backward/gqa/window/attn/core"
    assert got.layer == "kernels" and got.direction == "backward"
    plain_name = classify_op_name(
        "jit(_device_step)/step/forward_backward/jvp(AfmoeLM)/layer2/mixer/"
        "core/q_norm/mul")
    assert plain_name.scope == "step/forward_backward"


def test_the_compiled_step_names_the_decoders_layers(built):
    """Every scope of the decoder reaches the compiled program's
    instruction names, `attn/core` nested in the window's and the global
    layer's own."""
    scopes = built.scopes()
    for needle in ("gqa/proj", "gqa/window/attn/core", "gqa/global/attn/core",
                   "moe/route", "moe/experts", "moe/dispatch", "moe/plan",
                   "moe/shared", "lm/loss"):
        assert any(needle in s for s in scopes), (needle, sorted(scopes))


def test_trainer_takes_the_loss_from_the_model_and_counts():
    counters = checks.trainer_fits(FAMILY, 1e-3, epochs=2)
    assert counters["moe/pool_fill"]["count"] == 8
    assert 0.0 < counters["moe/pool_fill"]["max"] <= 1.0
