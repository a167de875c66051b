"""The AFMoE decoder (`models/afmoe.py`: gated grouped-query attention in
window and global layers, four norms a block, the shared feed-forward
half) against the plain reference's equations
(`benchmark/references/afmoe.py`), at tiny sizes on seeded weights: the
model's loss and gradient, the shares of an expert layer, rotary on window
layers only, rematerialisation, k and v at their own head count, and the
new scopes."""
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

from benchmark.references import afmoe as plain  # noqa: E402
from benchmark.references.numerics import Numerics  # noqa: E402
from geomx_tpu.models import afmoe, get_model  # noqa: E402
from geomx_tpu.models import decoder, kimi_linear  # noqa: E402

NX = Numerics("float32")

# 8 query heads on 2 key/value heads of 16; a band of 12 keys over 40
TINY = dict(vocab=64, hidden=32, num_heads=8, num_kv_heads=2, head_dim=16,
            window=12, rope_theta=10000.0, dense_width=48, expert_width=24,
            num_experts=16, experts_held=4, expert_offset=4, top_k=4,
            routed_scaling=2.826, embedding_scale=math.sqrt(32),
            layers=(("window", "mlp"), ("window", "moe"), ("global", "moe")))
PROGRAM = dict(loss_block=32, expert_rows=8, expert_pool=64)


def tiny_model_and_batch(**over):
    model = get_model("afmoe", **{**TINY, **PROGRAM, **over})
    tokens = np.random.default_rng(0).integers(0, 64, (2, 41))
    x, y = jnp.asarray(tokens[:, :-1]), jnp.asarray(tokens[:, 1:])
    variables = jax.jit(lambda: model.init(jax.random.PRNGKey(1), x))()
    # norms' scales off one, so that a norm left out or misplaced shows
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a + 0.1 * jax.random.normal(
            jax.random.PRNGKey(len(path)), a.shape)
        if path[-1].key == "scale" else a, variables["params"])
    return model, {"params": params}, x, y


def reference_sizes(**over):
    return {**TINY, "eps": 1e-5, **over}


def loss_of(model, x, y):
    return lambda p: model.apply({"params": p}, x, y,
                                 method="loss_and_aux")[0]


def test_the_shared_pieces_have_one_copy():
    for name in ("RMSNorm", "MLP", "swiglu", "route", "HeldExpertsLayer",
                 "blocked_cross_entropy", "MixerBranch", "FFNBranch", "Block"):
        assert getattr(kimi_linear, name) is getattr(decoder, name), name
    assert issubclass(afmoe.AfmoeLM, decoder.DecoderLM)
    assert issubclass(kimi_linear.KimiLinearLM, decoder.DecoderLM)


def test_model_loss_and_gradient_equal_the_plain_reference():
    model, variables, x, y = tiny_model_and_batch()
    ours = loss_of(model, x, y)
    theirs = lambda p: plain.loss(p, x, y, reference_sizes(), NX)
    params = variables["params"]
    np.testing.assert_allclose(ours(params), theirs(params), rtol=2e-6)
    got, want = jax.grad(ours)(params), jax.grad(theirs)(params)
    norm = np.sqrt(sum(float(jnp.sum(w * w)) for w in jax.tree.leaves(want)))
    off = np.sqrt(sum(float(jnp.sum((g - w) ** 2)) for g, w in
                      zip(jax.tree.leaves(got), jax.tree.leaves(want))))
    assert off / norm < 2e-5
    # a block's four norms, the q/k norms and the final one
    names = [p[-1].key for p, _ in
             jax.tree_util.tree_flatten_with_path(params)[0]]
    assert names.count("scale") == 3 * (4 + 2) + 1


def test_whole_logits_agree_with_the_blocked_loss_and_the_reference():
    model, variables, x, y = tiny_model_and_batch()
    logits = model.apply(variables, x)
    np.testing.assert_allclose(
        logits, plain.logits(variables["params"], x, reference_sizes(), NX),
        atol=3e-5)
    loss, aux = model.apply(variables, x, y, method="loss_and_aux")
    logz = jax.nn.logsumexp(logits, -1)
    picked = jnp.take_along_axis(logits, y[..., None], -1)[..., 0]
    np.testing.assert_allclose(loss, jnp.mean(logz - picked), rtol=1e-6)
    assert float(aux["counters"]["moe/dropped"]) == 0.0
    assert 0 < float(aux["counters"]["moe/assignments_mean"]) <= 80


def test_the_kernels_give_what_the_dense_fall_back_gives():
    """The same model through the grouped, windowed Pallas kernels
    (interpreted) and through the dense fall-back a CPU takes."""
    from geomx_tpu.ops import dispatch
    model, variables, x, y = tiny_model_and_batch()
    ours = loss_of(model, x, y)
    want = jax.value_and_grad(ours)(variables["params"])
    with dispatch.kernels("interpret"):
        got = jax.value_and_grad(ours)(variables["params"])
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    for a, b in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


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
    uncut = plain.moe(NX, x, whole, 0, 4, 2.826)
    tokens = x.reshape(-1, hidden)
    shared = plain.swiglu(NX, tokens, whole["shared_gate_kernel"],
                          whole["shared_up_kernel"],
                          whole["shared_down_kernel"]).reshape(x.shape)
    total, arrived = shared, 0
    for share in range(4):
        lo = 4 * share
        part = {k: (v[lo:lo + 4] if k.startswith("experts_") else v)
                for k, v in whole.items()}
        layer = decoder.HeldExpertsLayer(experts, 4, lo, 4, width, 2.826,
                                         rows=8, pool=32)
        y, counts, dropped = layer.apply({"params": part}, x)
        np.testing.assert_allclose(
            y, plain.moe(NX, x, part, lo, 4, 2.826), atol=2e-5)
        total = total + (y - shared)
        arrived += int(jnp.sum(counts))
        assert int(dropped) == 0
    np.testing.assert_allclose(total, uncut, atol=5e-5)
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
        params = mixer(window).init(jax.random.PRNGKey(1), x)
        last[name] = [mixer(window).apply(params, v)[0, -1]
                      for v in (x, swapped)]
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
    params = model.init(jax.random.PRNGKey(1), x)
    at = lambda v: model.apply(params, v)[0, 9]
    np.testing.assert_allclose(at(x.at[0, 5].add(1.0)), at(x), atol=1e-6)
    assert float(jnp.max(jnp.abs(at(x.at[0, 6].add(1.0)) - at(x)))) > 1e-3


def test_rematerialisation_changes_no_number():
    grads = []
    for remat in (True, False):
        model, variables, x, y = tiny_model_and_batch(
            remat=remat, layers=(("window", "moe"), ("global", "mlp")))
        grads.append(jax.grad(loss_of(model, x, y))(variables["params"]))
    for a, b in zip(*map(jax.tree.leaves, grads)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else [value]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


@pytest.mark.parametrize("mode", ["dense-fall-back", "interpret"])
def test_k_and_v_are_never_widened_to_the_query_heads(mode):
    """8 query heads read 2 key/value heads: no equation of the step, in
    any nested program (the per-sequence scan, the rematerialised halves,
    the attention's own forward and backward rules), takes an array laid
    out as k or v are ([1, L, 2, 16]) and gives one four times as large,
    which is what a repeat, a broadcast or a gather to 8 heads would do;
    and dk, dv come back with 2 heads."""
    from geomx_tpu.ops import dispatch
    model, variables, x, y = tiny_model_and_batch()
    fn = jax.value_and_grad(loss_of(model, x, y))
    if mode == "interpret":
        with dispatch.kernels("interpret"):
            jaxpr = jax.make_jaxpr(fn)(variables["params"])
    else:
        jaxpr = jax.make_jaxpr(fn)(variables["params"])
    kv_shape, seen = (1, 40, 2, 16), 0
    for eqn in _equations(jaxpr.jaxpr):
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


def test_the_compiled_step_names_the_decoders_layers():
    """Every scope of the decoder reaches the compiled program's
    instruction names, `attn/core` nested in the window's and the global
    layer's own."""
    from geomx_tpu.telemetry.layers import op_layers
    from geomx_tpu.utils.profiler import profile_scope
    model, variables, x, y = tiny_model_and_batch()

    def step(p):
        with profile_scope("step/forward_backward"):
            return jax.grad(loss_of(model, x, y))(p)

    text = jax.jit(step).lower(variables["params"]).compile().as_text()
    scopes = {entry.scope for entry in op_layers(text).values()
              if entry.scope}
    for needle in ("gqa/proj", "gqa/window/attn/core", "gqa/global/attn/core",
                   "moe/route", "moe/experts", "moe/dispatch", "moe/plan",
                   "moe/shared", "lm/loss"):
        assert any(needle in s for s in scopes), (needle, sorted(scopes))


def test_trainer_takes_the_loss_from_the_model_and_counts():
    """`Trainer.fit` on the decoder with no branch on its name: per-token
    labels through the loader, the model's loss in the step, its counters
    in `LoopStats`."""
    import optax
    from geomx_tpu import GeoConfig, HiPSTopology
    from geomx_tpu.sync import get_sync_algorithm
    from geomx_tpu.train import Trainer
    cfg = GeoConfig(num_parties=1, workers_per_party=1, sync_mode="fsa",
                    compression="none")
    topo = HiPSTopology(1, 1)
    model = get_model("afmoe", **{**TINY, **PROGRAM})
    trainer = Trainer(model, topo, optax.adam(1e-3),
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
    assert counters["moe/pool_fill"]["count"] == 8
    assert 0.0 < counters["moe/pool_fill"]["max"] <= 1.0
