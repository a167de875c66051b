"""The looped decoder (`models/ouro.py`: `decoder.DecoderLM` with `loops`,
its exit gate and expected-exit loss, `afmoe.GQAMixer` with neither q/k
norm nor gate) against the plain reference's equations
(`benchmark/references/ouro.py`), at tiny sizes on seeded weights: the
total loss, each step's loss and exit mass, the entropy and every
gradient leaf; a layer's gradient as the sum of its four uses'; the
normed stream feeding the next pass; the exit distribution; the weighted
blocked loss; the mixer's two forms; the loop as a scan, which was measured
and not kept; one loop and no gate as a plain decoder; and the other
decoders' parameter trees."""
import dataclasses
import hashlib

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_checks as checks
import test_glm4_moe_lite
from benchmark.references import ouro as plain
from geomx_tpu.models import afmoe, decoder, get_model, ouro
from geomx_tpu.ops import dispatch
from geomx_tpu.ops.flash_attention import fused_attention
from geomx_tpu.ops.gqa_elementwise import gated_ref

# 2 heads of 16 (key/value heads = query heads, as 16 on 16), two blocks,
# four loop steps
TINY = dict(vocab=64, hidden=32, num_heads=2, num_kv_heads=2, head_dim=16,
            rope_theta=10000.0, dense_width=48,
            layers=(("global", "mlp"),) * 2, loops=4, exit_beta=0.05)
PROGRAM = dict(loss_block=32)
REFERENCE = {**TINY, "eps": 1e-6}

NX = checks.NX
FAMILY = checks.Family("ouro", {**TINY, **PROGRAM}, plain, REFERENCE)


@pytest.fixture(scope="module")
def built():
    return checks.Built(FAMILY)


@pytest.fixture(scope="module")
def reference_parts(built):
    """(total, each step's loss, each step's mass, entropy) by the plain
    reference."""
    return jax.jit(lambda p: plain.losses(
        p, built.x, built.y, REFERENCE, NX))(built.params)


def test_the_shared_pieces_have_one_copy():
    assert issubclass(ouro.OuroLM, decoder.DecoderLM)
    cfg = ouro.OuroConfig(**TINY)
    mixer = cfg.make_mixer("global", jnp.float32)
    assert type(mixer) is afmoe.GQAMixer
    assert (mixer.window, mixer.rope, mixer.gated, mixer.qk_norm) == (
        None, 10000.0, False, False)
    assert (cfg.post_norms, cfg.embedding_scale, cfg.loops, cfg.exit_beta) \
        == (True, 1.0, 4, 0.05)
    with pytest.raises(ValueError, match="no mixer"):
        cfg.make_mixer("window", jnp.float32)
    with pytest.raises(ValueError, match="dense MLP"):
        ouro.OuroConfig(**{**TINY, "layers": (("global", "moe"),)})


def test_model_loss_and_every_gradient_leaf_equal_the_plain_reference(built):
    got, want = checks.loss_equals_the_reference(built)
    flat = jax.tree_util.tree_flatten_with_path(want)[0]
    for (path, w), g in zip(flat, jax.tree.leaves(got)):
        scale = float(jnp.max(jnp.abs(w)))
        assert scale > 0, path          # every leaf takes part
        np.testing.assert_allclose(g, w, atol=3e-5 * scale, err_msg=str(path))
    names = ["/".join(k.key for k in p) for p, _ in flat]
    # one stack's parameters whatever the loop count; the gate's pair
    assert [n for n in names if not n.startswith("layer")] == [
        "embedding", "exit_gate/bias", "exit_gate/kernel",
        "final_norm/scale", "head_kernel"]
    assert sorted(n for n in names if n.startswith("layer1/")) == [
        "layer1/ffn/core/down_kernel", "layer1/ffn/core/gate_kernel",
        "layer1/ffn/core/up_kernel", "layer1/ffn/norm/scale",
        "layer1/ffn/post_norm/scale", "layer1/mixer/core/k_kernel",
        "layer1/mixer/core/out_kernel", "layer1/mixer/core/q_kernel",
        "layer1/mixer/core/v_kernel", "layer1/mixer/norm/scale",
        "layer1/mixer/post_norm/scale"]
    assert built.params["exit_gate"]["kernel"].shape == (32, 1)
    assert built.params["exit_gate"]["bias"].shape == (1,)


def test_each_steps_loss_and_mass_and_the_entropy_are_the_references(
        built, reference_parts):
    """The counters beside the total: `loop/loss_t`, `loop/exit_mass_t`,
    `loop/exit_entropy`, and `lm/main_loss` = total + beta x entropy;
    `accuracy` is the last step's head's."""
    (loss, aux), _ = built.ours
    total, ces, masses, entropy = reference_parts
    counters = aux["counters"]
    assert set(counters) == {"lm/main_loss", "loop/exit_entropy"} | {
        f"loop/{kind}_{t}" for kind in ("loss", "exit_mass")
        for t in (1, 2, 3, 4)}
    np.testing.assert_allclose(loss, total, rtol=2e-6)
    for t in range(4):
        np.testing.assert_allclose(counters[f"loop/loss_{t + 1}"], ces[t],
                                   rtol=2e-6)
        np.testing.assert_allclose(counters[f"loop/exit_mass_{t + 1}"],
                                   masses[t], rtol=2e-6)
    np.testing.assert_allclose(counters["loop/exit_entropy"], entropy,
                               rtol=2e-6)
    np.testing.assert_allclose(counters["lm/main_loss"],
                               total + 0.05 * entropy, rtol=2e-6)
    np.testing.assert_allclose(float(jnp.sum(masses)), 1.0, rtol=1e-6)
    # the steps differ: four losses, four masses, every step holds mass
    assert len({round(float(c), 4) for c in ces}) == 4
    assert float(jnp.min(masses)) > 0.01 and 0.5 < float(entropy) < np.log(4)
    logits = jax.jit(lambda p: built.model.apply({"params": p}, built.x))(
        built.params)
    assert float(aux["accuracy"]) == float(
        jnp.mean(jnp.argmax(logits, -1) == built.y))


def test_whole_logits_are_the_last_steps(built):
    f = built.family
    logits = jax.jit(lambda p: built.model.apply({"params": p}, built.x))(
        built.params)
    want = jax.jit(lambda p: plain.logits(p, built.x, f.reference_sizes, NX))(
        built.params)
    np.testing.assert_allclose(logits, want, atol=3e-5)
    (_, aux), _ = built.ours
    picked = jnp.take_along_axis(logits, built.y[..., None], -1)[..., 0]
    np.testing.assert_allclose(
        aux["counters"]["loop/loss_4"],
        jnp.mean(jax.nn.logsumexp(logits, -1) - picked), rtol=1e-6)


def test_a_layers_gradient_is_the_sum_of_its_four_uses(built):
    """The reference with four untied copies of the stack (equal values),
    one a loop step: the copies' gradients, summed, are the shared
    layers' gradient in the model; each use's is a part of it."""
    stack = {k: v for k, v in built.params.items()
             if k.startswith("layer") or k == "final_norm"}

    def untied(stacks):
        return plain.loss(built.params, built.x, built.y, REFERENCE, NX,
                          stacks=stacks)
    uses = jax.jit(jax.grad(untied))([stack] * 4)
    summed = jax.tree.map(lambda *g: sum(g), *uses)
    (_, _), got = built.ours
    for name in stack:
        for (path, w), g in zip(
                jax.tree_util.tree_flatten_with_path(summed[name])[0],
                jax.tree.leaves(got[name])):
            scale = float(jnp.max(jnp.abs(w)))
            np.testing.assert_allclose(g, w, atol=3e-5 * scale,
                                       err_msg=f"{name}{path}")
    # no single use is the whole: the last pass alone misses most of it
    last = uses[-1]["layer1"]["ffn"]["core"]["up_kernel"]
    whole = got["layer1"]["ffn"]["core"]["up_kernel"]
    assert float(jnp.linalg.norm(whole - last)) > 0.2 * float(
        jnp.linalg.norm(whole))


class Unnormed(ouro.OuroLM):
    """The fault: the next pass reads the stream BEFORE the final norm."""

    def features(self, tokens):
        h, streams = self.embed(tokens), []
        for _ in range(self.loops):
            normed, counts, lost, h = self.stack(h)
            streams.append(normed)
        return jnp.stack(streams), counts, lost, h


def test_the_normed_stream_feeds_the_next_pass(built):
    wrong = Unnormed(ouro.OuroConfig(**TINY, **PROGRAM))
    loss, aux = jax.jit(lambda p: wrong.apply(
        {"params": p}, built.x, built.y, method="loss_and_aux"))(built.params)
    (ours, ours_aux), _ = built.ours
    # the first pass is the same, every later one reads another stream
    np.testing.assert_allclose(aux["counters"]["loop/loss_1"],
                               ours_aux["counters"]["loop/loss_1"], rtol=1e-6)
    for t in (2, 3, 4):
        assert abs(float(aux["counters"][f"loop/loss_{t}"])
                   - float(ours_aux["counters"][f"loop/loss_{t}"])) > 1e-3
    assert abs(float(loss) - float(ours)) > 1e-3


class Scanned(ouro.OuroLM):
    """The other form PR 48 measured and did not keep: ONE `nn.scan` over
    the loop steps with the parameters broadcast, so that the compiled
    step holds the stack once (a shorter compile, 1.35 GiB less, a fifth
    slower on the chip: PERF.md section 6)."""

    def features(self, tokens):
        def one_pass(model, h):
            normed, counts, lost, stream = model.stack(h)
            return normed, (normed, counts, lost, stream)
        _, (streams, arrived, dropped, before) = nn.scan(
            one_pass, variable_broadcast="params",
            split_rngs={"params": False}, length=self.loops)(
            self, self.embed(tokens))
        return streams, arrived.reshape(-1), jnp.sum(dropped), before[-1]


def test_scanned_and_unrolled_loops_agree(built):
    """The program's four passes one after the other against one scan
    over the loop steps: the same tree, the same numbers; the scan holds
    each block's per-sequence loop once, the program four times."""
    scanned = Scanned(ouro.OuroConfig(**TINY, **PROGRAM))
    shapes = jax.eval_shape(lambda: scanned.init(jax.random.PRNGKey(1),
                                                 built.x))["params"]
    assert jax.tree.structure(shapes) == jax.tree.structure(built.params)
    lowered = built.step_of(scanned).lower(built.params)
    (want_loss, want_aux), want = built.ours
    (loss, aux), got = lowered.compile()(built.params)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    for key, value in want_aux["counters"].items():
        np.testing.assert_allclose(aux["counters"][key], value, rtol=2e-6)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-6)
    count = lambda low: low.as_text().count("stablehlo.while")
    assert count(built.lowered) > 2 * count(lowered)


def test_the_kernels_give_what_the_dense_fall_back_gives(built):
    checks.kernels_give_the_dense_fall_back(built)


def test_rematerialisation_changes_no_number(built):
    checks.rematerialisation_changes_no_number(built, rtol=1e-4, atol=1e-5)


# ---- the exit distribution ------------------------------------------------

def test_the_exit_masses_sum_to_one_and_the_last_takes_the_rest():
    a = 3.0 * jax.random.normal(jax.random.PRNGKey(0), (4, 50))
    a = a.at[:, 0].set(jnp.asarray([60.0, -60.0, 0.0, 5.0]))    # saturated
    a = a.at[:, 1].set(-200.0)                  # never exits before the end
    log_p = decoder.exit_distribution(a)
    assert bool(jnp.all(jnp.isfinite(log_p)))
    p = jnp.exp(log_p)
    np.testing.assert_allclose(jnp.sum(p, 0), 1.0, rtol=2e-6)
    lam = jax.nn.sigmoid(a)
    stay = jnp.cumprod(1.0 - lam, axis=0)
    np.testing.assert_allclose(p[0], lam[0], rtol=1e-4, atol=1e-20)
    np.testing.assert_allclose(p[1], lam[1] * stay[0], rtol=1e-4, atol=1e-20)
    np.testing.assert_allclose(p[2], lam[2] * stay[1], rtol=1e-4, atol=1e-20)
    np.testing.assert_allclose(p[3], stay[2], rtol=1e-4, atol=1e-20)
    assert float(p[3, 1]) == 1.0            # the last step took everything
    # the last step's own gate is never asked
    other = decoder.exit_distribution(a.at[3].set(-a[3]))
    np.testing.assert_array_equal(other, log_p)
    np.testing.assert_allclose(log_p, plain.exit_masses(a), rtol=1e-6,
                               atol=1e-6)
    # the planted fault falls short of 1 by lambda^T's complement
    short = jnp.exp(plain.exit_masses(a, last_takes_rest=False))
    np.testing.assert_allclose(jnp.sum(short, 0),
                               1.0 - stay[2] * (1.0 - lam[3]), rtol=1e-5)
    # and its gradient is finite where a sigmoid saturates
    grads = jax.grad(lambda a_: jnp.sum(
        jnp.exp(decoder.exit_distribution(a_)) * jnp.arange(4.0)[:, None]))(a)
    assert bool(jnp.all(jnp.isfinite(grads)))


# ---- the weighted blocked loss --------------------------------------------

def loss_inputs(rows=70):
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    return (jax.random.normal(ks[0], (rows, 32)),
            jax.random.normal(ks[1], (32, 64)) * 32 ** -0.5,
            jax.random.randint(ks[2], (rows,), 0, 64))


def test_the_blocked_loss_without_weights_gives_the_autodiff_bodys_numbers():
    """No `weights`: the function the decoders call against the body this
    file keeps of what it was until PR 49 (a checkpointed scan that JAX
    differentiates: four products a block where the rule runs three): the
    same total, hits and gradients."""
    h, head, labels = loss_inputs()

    def todays(h, head, labels, block):
        t = h.shape[0]
        block = min(block, t)
        pad = (-t) % block
        if pad:
            h = jnp.pad(h, ((0, pad), (0, 0)))
            labels = jnp.pad(labels, (0, pad), constant_values=-1)

        @jax.checkpoint
        def one(carry, xs):
            h_, y_ = xs
            logits = jnp.dot(h_, head, preferred_element_type=jnp.float32)
            logz = jax.nn.logsumexp(logits, axis=-1)
            picked = jnp.take_along_axis(
                logits, jnp.maximum(y_, 0)[:, None], axis=-1)[:, 0]
            real = y_ >= 0
            hits = jnp.sum(real & (jnp.argmax(logits, -1) == y_))
            return (carry[0] + jnp.sum(jnp.where(real, logz - picked, 0.0)),
                    carry[1] + hits.astype(jnp.float32)), None

        (total, hits), _ = jax.lax.scan(
            one, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
            (h.reshape(-1, block, h.shape[-1]),
             labels.astype(jnp.int32).reshape(-1, block)))
        return total, hits

    numbers = lambda f: jax.jit(jax.value_and_grad(
        lambda h_, w_: f(h_, w_, labels, 32), (0, 1), has_aux=True))(h, head)
    (got, got_hits), got_grads = numbers(decoder.blocked_cross_entropy)
    (want, want_hits), want_grads = numbers(todays)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert float(got_hits) == float(want_hits)
    for got_grad, want_grad in zip(got_grads, want_grads):
        np.testing.assert_allclose(got_grad, want_grad, atol=1e-6)


@pytest.mark.parametrize("groups, block", [(1, 32), (2, 16), (5, 32), (2, 64)])
def test_weights_of_ones_give_the_unweighted_loss(groups, block):
    """A run's rows are blocked on their own (35 rows of a run pad to
    blocks of 16 or 32); the groups' sums and hits add up to the whole."""
    h, head, labels = loss_inputs()
    total, hits = jax.jit(lambda: decoder.blocked_cross_entropy(
        h, head, labels, block))()
    weighted, group_hits, sums = jax.jit(lambda: decoder.blocked_cross_entropy(
        h, head, labels, block, jnp.ones((70,)), groups))()
    np.testing.assert_allclose(weighted, total, rtol=1e-6)
    np.testing.assert_allclose(jnp.sum(sums), total, rtol=1e-6)
    assert group_hits.shape == sums.shape == (groups,)
    assert float(jnp.sum(group_hits)) == float(hits)
    each = jax.nn.logsumexp(h @ head, -1) - jnp.take_along_axis(
        h @ head, labels[:, None], -1)[:, 0]
    np.testing.assert_allclose(
        sums, jnp.sum(each.reshape(groups, -1), 1), rtol=1e-5)


def test_the_gradient_in_the_weights_is_each_rows_cross_entropy():
    h, head, labels = loss_inputs()
    weights = jax.random.uniform(jax.random.PRNGKey(5), (70,))
    f = lambda w, h_, head_: decoder.blocked_cross_entropy(
        h_, head_, labels, 16, w, 2)[0]
    dw, dh, dhead = jax.jit(jax.grad(f, (0, 1, 2)))(weights, h, head)
    logits = h @ head
    each = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, labels[:, None], -1)[:, 0]
    np.testing.assert_allclose(dw, each, rtol=1e-5)
    want = jax.grad(lambda h_, head_: jnp.sum(weights * (
        jax.nn.logsumexp(h_ @ head_, -1) - jnp.take_along_axis(
            h_ @ head_, labels[:, None], -1)[:, 0])), (0, 1))(h, head)
    np.testing.assert_allclose(dh, want[0], atol=1e-6)
    np.testing.assert_allclose(dhead, want[1], atol=1e-5)
    # one block of logits alive at a time: the scan's body holds [16, 64]
    jaxpr = jax.make_jaxpr(jax.grad(f))(weights, h, head)
    shapes = [tuple(getattr(v.aval, "shape", ()))
              for e in checks.equations(jaxpr.jaxpr) for v in e.outvars]
    assert max(s[0] for s in shapes if len(s) == 2 and s[1] == 64
               and s[0] != 32) == 16


# ---- the mixer's two forms ------------------------------------------------

class YesterdaysMixer(nn.Module):
    """`models/afmoe.GQAMixer` as the parent of PR 48 had it, kept here as
    the oracle of `qk_norm=True`: always the per-head norms."""
    num_heads: int
    num_kv_heads: int
    head_dim: int
    window: object
    rope: object
    eps: float
    gated: bool = True

    @nn.compact
    def __call__(self, x):
        h, kv, d = self.num_heads, self.num_kv_heads, self.head_dim
        b, length, hidden = x.shape
        mat = lambda name, shape: self.param(name, decoder._fan_in, shape)

        def heads(name, n):
            return jnp.dot(x, mat(name, (hidden, n * d))).reshape(
                b, length, n, d)

        q, k = dispatch.gqa_norm_rotary(
            heads("q_kernel", h), heads("k_kernel", kv),
            decoder.HeadScale(name="q_norm")(d),
            decoder.HeadScale(name="k_norm")(d), self.eps, self.rope)
        v = heads("v_kernel", kv)
        if self.gated:
            gate = jnp.dot(x, mat("gate_kernel", (hidden, h * d)),
                           preferred_element_type=jnp.float32)
        o = fused_attention(q, k, v, True, False, self.window)
        o = o.reshape(b, length, h * d)
        if self.gated:
            o = gated_ref(o, gate)
        return jnp.dot(o, mat("out_kernel", (h * d, hidden)))


@pytest.mark.parametrize("form", ["trinity-window", "trinity-global",
                                  "mellum"])
def test_with_its_norms_the_mixer_is_yesterdays(form):
    """`qk_norm=True` (the default): Trinity's and Mellum's parameter
    names, seeded values, output and gradients, in every bit."""
    window, rope, gated = {"trinity-window": (12, 10000.0, True),
                           "trinity-global": (None, None, True),
                           "mellum": (12, 10000.0, False)}[form]
    sizes = (8, 2, 16, window, rope, 1e-5)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 20, 32))
    old = YesterdaysMixer(*sizes, gated=gated)
    new = afmoe.GQAMixer(*sizes, gated=gated)
    params = jax.jit(old.init)(jax.random.PRNGKey(1), x)
    fresh = jax.jit(new.init)(jax.random.PRNGKey(1), x)
    assert jax.tree.structure(params) == jax.tree.structure(fresh)
    assert sorted(params["params"]) == sorted(
        ["k_kernel", "k_norm", "out_kernel", "q_kernel", "q_norm",
         "v_kernel"] + ["gate_kernel"] * gated)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(fresh)):
        np.testing.assert_array_equal(a, b)
    run = lambda m: jax.jit(jax.value_and_grad(
        lambda p, x_: jnp.sum(jnp.sin(m.apply(p, x_))), (0, 1)))(params, x)
    (want, want_grads), (got, got_grads) = run(old), run(new)
    assert float(got) == float(want)
    for a, b in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads)):
        np.testing.assert_array_equal(a, b)


def test_without_its_norms_the_mixer_is_the_references_attention():
    mixer = ouro.OuroConfig(**TINY).make_mixer("global", jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 20, 32))
    params = jax.jit(mixer.init)(jax.random.PRNGKey(1), x)
    assert sorted(params["params"]) == ["k_kernel", "out_kernel", "q_kernel",
                                        "v_kernel"]
    want = plain.attention(NX, x, params["params"], REFERENCE)
    np.testing.assert_allclose(jax.jit(mixer.apply)(params, x), want,
                               atol=2e-5)
    # positions matter: swapping two earlier tokens moves the last output
    swapped = x.at[:, 2].set(x[:, 5]).at[:, 5].set(x[:, 2])
    last = [jax.jit(mixer.apply)(params, v)[:, -1] for v in (x, swapped)]
    assert float(jnp.max(jnp.abs(last[0] - last[1]))) > 1e-3
    # with no positions either it is blind to the swap
    blind = afmoe.GQAMixer(2, 2, 16, None, None, 1e-6, gated=False,
                           qk_norm=False)
    last = [jax.jit(blind.apply)(params, v)[:, -1] for v in (x, swapped)]
    assert float(jnp.max(jnp.abs(last[0] - last[1]))) < 1e-5
    # grouped heads read their own key/value head
    grouped = afmoe.GQAMixer(4, 2, 16, None, 10000.0, 1e-6, gated=False,
                             qk_norm=False)
    p = jax.jit(grouped.init)(jax.random.PRNGKey(2), x)
    want = plain.attention(NX, x, p["params"], {
        **REFERENCE, "num_heads": 4, "num_kv_heads": 2})
    np.testing.assert_allclose(jax.jit(grouped.apply)(p, x), want, atol=2e-5)


# ---- one loop, no gate ----------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PlainConfig:
    """A configuration that has never heard of loops: `OuroConfig`'s
    widths without `loops` and `exit_beta`."""
    vocab: int
    hidden: int
    layers: tuple
    num_heads: int
    head_dim: int
    rope_theta: float
    dense_width: int
    eps: float = 1e-6
    loss_block: int = 32
    remat: bool = True
    post_norms = True
    embedding_scale = 1.0

    def make_mixer(self, kind, dtype):
        return afmoe.GQAMixer(self.num_heads, self.num_heads, self.head_dim,
                              None, self.rope_theta, self.eps, dtype,
                              gated=False, qk_norm=False, name="core")


def test_one_loop_and_no_gate_is_a_plain_decoder():
    """`loops` 1: no `exit_gate` leaf, the mean next-token loss, no
    counters, and the lowered step is, letter for letter, that of a
    `DecoderLM` whose configuration has no loop at all."""
    once = checks.Built(checks.Family(
        "ouro", {**TINY, **PROGRAM, "loops": 1}, plain,
        {**REFERENCE, "loops": 1}))
    assert "exit_gate" not in once.params
    keys = ("vocab", "hidden", "layers", "num_heads", "head_dim",
            "rope_theta", "dense_width")
    never = decoder.DecoderLM(PlainConfig(**{k: TINY[k] for k in keys}))
    shapes = jax.eval_shape(lambda: never.init(jax.random.PRNGKey(1),
                                               once.x))["params"]
    assert jax.tree.structure(shapes) == jax.tree.structure(once.params)
    (loss, aux), grads = once.ours
    assert set(aux) == {"accuracy"}
    gate = {"kernel": jnp.zeros((32, 1)), "bias": jnp.zeros((1,))}
    total, ces, masses, entropy = jax.jit(lambda p: plain.losses(
        {**p, "exit_gate": gate}, once.x, once.y, {**REFERENCE, "loops": 1},
        NX))(once.params)
    np.testing.assert_allclose(loss, total, rtol=2e-6)
    np.testing.assert_allclose(loss, ces[0], rtol=2e-6)
    assert float(masses[0]) == 1.0 and float(entropy) == 0.0
    theirs = checks.Built.step_of(once, never).lower(once.params).as_text()
    ours = once.lowered.as_text()
    assert "while" in ours and ours.replace("OuroLM", "DecoderLM") == theirs
    text = once.lowered.as_text(debug_info=True)
    assert "loop/exit" not in text and "ffn/mlp" in text


def test_no_multi_token_prediction_behind_a_looped_stack():
    cfg = test_glm4_moe_lite.glm4_moe_lite.Glm4MoeLiteConfig(
        **test_glm4_moe_lite.TINY)
    looped = dataclasses.make_dataclass(
        "Looped", [("loops", int, 2), ("exit_beta", float, 0.0)],
        bases=(type(cfg),), frozen=True)(**dataclasses.asdict(cfg))
    with pytest.raises(ValueError, match="looped stack"):
        jax.eval_shape(lambda: decoder.DecoderLM(looped).init(
            jax.random.PRNGKey(0), np.zeros((1, 8), np.int32)))


# ---- tracing ---------------------------------------------------------------

def test_the_compiled_step_names_the_loops_layers(built):
    from geomx_tpu.telemetry.layers import classify_op_name, layer_of
    assert layer_of("ffn/mlp") == layer_of("loop/exit") == "step program"
    scopes = built.scopes()
    for needle in ("gqa/proj", "gqa/global/attn/core", "ffn/mlp", "loop/exit",
                   "lm/loss"):
        assert any(needle in s for s in scopes), (needle, sorted(scopes))
    # the head passes stand under lm/loss, not under the gate's scope
    assert not any("loop/exit" in s and "lm/loss" in s for s in scopes)
    got = classify_op_name(
        "jit(_device_step)/step/forward_backward/transpose(jvp(OuroLM))/"
        "layer3/ffn/checkpoint/ffn/mlp/core/dot_general")
    assert got.scope == "step/forward_backward/ffn/mlp"
    assert got.layer == "step program" and got.direction == "backward"
    got = classify_op_name(
        "jit(_device_step)/step/forward_backward/jvp(OuroLM)/loop/exit/"
        "exit_gate/dot_general")
    assert got.scope == "step/forward_backward/loop/exit"
    # the gate's flax name never reads as a scope
    assert classify_op_name(
        "jit(step)/step/forward_backward/jvp(OuroLM)/exit_gate/add"
    ).scope == "step/forward_backward"


# ---- nothing else moved ---------------------------------------------------

# tiny configurations of the five other decoders and the digest of their
# parameter trees (paths and shapes, sorted) as the parent of PR 48 gave
# them: the three `test_mellum.py` pinned, Mellum's and GLM's own
OTHERS = {**test_glm4_moe_lite.OTHERS,
          "mellum": (test_glm4_moe_lite.OTHERS["mellum"][0], 39,
                     "d3877c9ad810253d"),
          "glm4_moe_lite": ({**test_glm4_moe_lite.TINY,
                             **test_glm4_moe_lite.PROGRAM}, 67,
                            "f64fd1df62243942")}


@pytest.mark.parametrize("name", sorted(OTHERS))
def test_the_other_decoders_parameter_trees_and_losses_are_unchanged(name):
    """No `exit_gate` leaf, the digests of the parent's trees, and
    `loss_and_aux` still one unweighted loss: no counter of the loop's,
    no `loop/exit` in the lowered step."""
    sizes, leaves, digest = OTHERS[name]
    model = get_model(name, **sizes)
    lines = test_glm4_moe_lite.tree_lines(model)
    assert not [line for line in lines if "exit_gate" in line]
    assert len(lines) == leaves
    assert hashlib.sha256("\n".join(lines).encode()).hexdigest()[:16] \
        == digest, lines
    x = np.zeros((1, 8), np.int32)
    step = lambda: model.apply(model.init(jax.random.PRNGKey(0), x), x, x,
                               method="loss_and_aux")
    aux = jax.eval_shape(step)[1]
    assert not [k for k in aux["counters"] if k.startswith("loop/")]
    text = jax.jit(step).lower().as_text(debug_info=True)
    assert "loop/exit" not in text and "lm/loss" in text
    dense = any(ffn == "mlp" for _, ffn in sizes["layers"])
    assert ("ffn/mlp" in text) == dense


def test_trainer_takes_the_expected_exit_loss_from_the_model_and_counts():
    """`get_model("ouro")` through `Trainer.fit`, the loader and FSA's
    dense tier as they are; the loop's counters come through
    `LoopStats`."""
    import optax
    from geomx_tpu import GeoConfig, HiPSTopology
    from geomx_tpu.sync import get_sync_algorithm
    from geomx_tpu.train import Trainer
    cfg = GeoConfig(num_parties=1, workers_per_party=1, sync_mode="fsa",
                    compression="none")
    trainer = Trainer(FAMILY.model(), HiPSTopology(1, 1), optax.adam(1e-3),
                      sync=get_sync_algorithm(cfg), config=cfg)
    tokens = np.random.default_rng(1).integers(0, 64, (8, 41)).astype(
        np.int32)
    x, y = tokens[:, :-1], tokens[:, 1:]
    state = trainer.init_state(jax.random.PRNGKey(0), x[:2])
    assert state.params["exit_gate"]["kernel"].shape[-2:] == (32, 1)
    state, records = trainer.fit(state, trainer.make_loader(x, y, 2),
                                 epochs=2, log_every=1,
                                 log_fn=lambda _line: None)
    losses = [r["loss"] for r in records if "loss" in r]
    assert len(losses) == 8 and losses[-1] < losses[0]
    counters = trainer.loop_stats.as_dict()["counters"]
    for t in (1, 2, 3, 4):
        assert counters[f"loop/loss_{t}"]["count"] == 8
        assert 0.0 < counters[f"loop/exit_mass_{t}"]["last"] < 1.0
    assert sum(counters[f"loop/exit_mass_{t}"]["last"]
               for t in (1, 2, 3, 4)) == pytest.approx(1.0, rel=1e-5)
    assert 0.0 < counters["loop/exit_entropy"]["last"] <= np.log(4) + 1e-6
    assert counters["lm/main_loss"]["last"] > losses[-1]
