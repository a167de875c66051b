"""The Nemotron-H decoder (`models/nemotron_h.py`: layers of ONE half, a
Mamba-2 mixer, plain grouped-query attention, a LatentMoE through
`decoder.HeldExpertsLayer`) against the plain reference's equations
(`benchmark/references/nemotron_h.py`), at tiny sizes on seeded weights:
the model's loss and every gradient leaf, single-half blocks, the un-gated
path of `ops/held_experts` against a loop over experts, the new scopes,
and **the shares add up**: the head shares of a Mamba-2 layer and of an
attention layer, and the expert shares of a LatentMoE, sum to the uncut
reference's layer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_checks as checks
from benchmark.references import nemotron_h as plain
from geomx_tpu.models import afmoe, decoder
from geomx_tpu.models import kimi_linear, nemotron_h
from geomx_tpu.ops.held_experts import held_experts

# the whole tiny layer: 8 Mamba-2 heads of 8 in 4 B/C groups of 16, 8
# query heads on 2 key/value heads of 16, 16 experts of 24 in a latent 16
WHOLE = dict(vocab=64, hidden=32, mamba_heads=8, mamba_head_dim=8,
             mamba_groups=4, state_size=16, conv_size=4, num_heads=8,
             num_kv_heads=2, head_dim=16, expert_width=24, shared_width=40,
             latent=16, num_experts=16, experts_held=16, expert_offset=0,
             top_k=6, routed_scaling=5.0)
# a chip's share of it: a quarter of the heads, a quarter of the experts
SHARE = dict(WHOLE, mamba_heads=2, mamba_groups=1, num_heads=2,
             num_kv_heads=1, experts_held=4, expert_offset=4)
LAYERS = (("mamba", None), (None, "moe"), ("attention", None), (None, "moe"),
          ("mamba", None))
PROGRAM = dict(ssd_chunk=16, loss_block=32, expert_rows=8, expert_pool=64)


def sizes(base, **over):
    return {**base, "layers": LAYERS, "eps": 1e-5, **over}


NX = checks.NX
FAMILY = checks.Family("nemotron_h", {**SHARE, "layers": LAYERS, **PROGRAM},
                       plain, sizes(SHARE))


@pytest.fixture(scope="module")
def built():
    return checks.Built(FAMILY)


def seeded(shapes, seed=0):
    """A dict of seeded normal arrays, fan-in scaled where a matrix."""
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    out = {}
    for key, (name, shape) in zip(keys, sorted(shapes.items())):
        std = shape[-2] ** -0.5 if len(shape) > 1 and "conv" not in name \
            else 0.5
        out[name] = std * jax.random.normal(key, shape)
    return out


def test_the_shared_pieces_have_one_copy():
    for name in ("HeadScale", "causal_conv"):
        assert getattr(nemotron_h, name) is getattr(decoder, name), name
    assert afmoe.HeadScale is decoder.HeadScale
    assert kimi_linear.causal_conv is decoder.causal_conv
    assert issubclass(nemotron_h.NemotronHLM, decoder.DecoderLM)
    # the accepted decoders' expert layers stay SwiGLU in the hidden width
    assert kimi_linear.KimiLinearConfig.expert_form == {}
    assert afmoe.AfmoeConfig.expert_form == {}


def test_model_loss_and_every_gradient_leaf_equal_the_plain_reference(built):
    got, want = checks.loss_equals_the_reference(built)
    whole = np.sqrt(sum(float(jnp.sum(w * w)) for w in jax.tree.leaves(want)))
    flat = jax.tree_util.tree_flatten_with_path(want)[0]
    assert len(flat) == len(jax.tree.leaves(got)) == 3 + 2 * 9 + 5 + 2 * 8
    for (path, w), g in zip(flat, jax.tree.leaves(got)):
        name = "/".join(k.key for k in path)
        assert g.shape == w.shape, name
        off = float(jnp.sqrt(jnp.sum((g - w) ** 2)))
        # against the leaf's own norm, or a thousandth of the whole
        # gradient's where the leaf's is all but nothing
        scale = max(float(jnp.sqrt(jnp.sum(w * w))), 1e-3 * whole)
        assert off / scale < 5e-5, (name, off / scale)
        assert float(jnp.sum(w * w)) > 0, name


def test_a_layer_is_one_half_with_one_norm(built):
    model, params = built.model, built.params
    for i, (mixer, ffn) in enumerate(LAYERS):
        layer = params[f"layer{i + 1}"]
        assert set(layer) == {"mixer" if ffn is None else "ffn"}
        half = layer["mixer" if ffn is None else "ffn"]
        assert set(half) == {"norm", "core"}
    assert set(params["layer1"]["mixer"]["core"]) == {
        "in_kernel", "conv_kernel", "conv_bias", "dt_bias", "A_log", "D",
        "out_norm", "out_kernel"}
    assert set(params["layer3"]["mixer"]["core"]) == {
        "q_kernel", "k_kernel", "v_kernel", "out_kernel"}
    assert set(params["layer2"]["ffn"]["core"]) == {
        "router_kernel", "shared_up_kernel", "shared_down_kernel",
        "latent_down_kernel", "latent_up_kernel", "experts_up_kernel",
        "experts_down_kernel"}
    core = params["layer1"]["mixer"]["core"]
    # z 16 + xBC (16 + 2 x 16) + dt 2 of the two heads held
    assert core["in_kernel"].shape == (32, 16 + 48 + 2)
    assert core["conv_kernel"].shape == (4, 48)
    assert core["out_norm"]["scale"].shape == (16,)
    # the halves of a two-half block still both run (`Block` elsewhere)
    block = decoder.Block("mamba", None, model.cfg)
    h = jnp.ones((2, 24, 32))
    out, counts, dropped = jax.jit(block.apply)(
        {"params": {"mixer": params["layer1"]["mixer"]}}, h)
    assert out.shape == h.shape and counts.shape == (0,) and dropped == 0
    only = decoder.Block(None, "moe", model.cfg)
    out, counts, dropped = jax.jit(only.apply)(
        {"params": {"ffn": params["layer2"]["ffn"]}}, h)
    assert out.shape == h.shape and counts.shape == (4,) and dropped == 0


def test_remat_changes_nothing(built):
    checks.rematerialisation_changes_no_number(built, rtol=5e-6, atol=5e-6)


@pytest.mark.parametrize("pool", [None, 8, 16, 40, 64, 128])
def test_ungated_held_experts_equal_a_loop_over_experts(pool):
    """`held_experts(..., gate=None, ...)`: relu(x W1)^2 W2 a held expert,
    weighted and summed, values and the gradients of x, the weights and
    both kernels; first pools too small for what arrives (one tile, two,
    five), the default one and two that hold everything."""
    tokens, d, f, held, offset, k = 48, 16, 24, 4, 4, 6
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(keys[0], (tokens, d))
    up = jax.random.normal(keys[1], (held, d, f)) * d ** -0.5
    down = jax.random.normal(keys[2], (held, f, d)) * f ** -0.5
    scores = jax.random.uniform(keys[3], (tokens, 16))
    _, idx = jax.lax.top_k(scores, k)
    weights = jnp.take_along_axis(scores, idx, -1)

    def kernel(x, weights, up, down):
        return held_experts(x, idx, weights, None, up, down, offset, 8,
                            True, pool)

    def loop(x, weights, up, down):
        dense = jnp.zeros((tokens, 16)).at[
            jnp.arange(tokens)[:, None], idx].set(weights)
        y = jnp.zeros_like(x)
        for e in range(held):
            y = y + dense[:, offset + e, None] * plain.relu2(
                NX, x, up[e], down[e])
        return y

    args = (x, weights, up, down)
    probe = jax.random.normal(keys[4], (tokens, d))
    (y, counts, dropped), got = checks.value_and_gradients(
        kernel, args, range(4), probe)
    want_y, want = checks.value_and_gradients(loop, args, range(4), probe)
    assert int(dropped) == 0
    assert int(jnp.sum(counts)) == int(jnp.sum(
        (idx >= offset) & (idx < offset + held)))
    np.testing.assert_allclose(y, want_y, atol=2e-5)
    for name, g, w in zip(("x", "weights", "up", "down"), got, want):
        np.testing.assert_allclose(g, w, atol=5e-5, err_msg=name)


def mamba_whole_and_shares(hidden=32):
    """The uncut layer's parameters under the reference's names, and each
    of the 4 shares' (one B/C group with its two heads): columns of W_in,
    the convolution's channels, the per-head scalars, the gated norm's
    group and rows of W_out."""
    heads, p, groups, n = 8, 8, 4, 16
    inner, bc = heads * p, groups * n
    whole = seeded({"in_kernel": (hidden, 2 * inner + 2 * bc + heads),
                    "conv_kernel": (4, inner + 2 * bc),
                    "conv_bias": (inner + 2 * bc,), "dt_bias": (heads,),
                    "A_log": (heads,), "D": (heads,),
                    "out_kernel": (inner, hidden)}, seed=5)
    whole["out_norm"] = {"scale": 1.0 + 0.1 * jax.random.normal(
        jax.random.PRNGKey(9), (inner,))}
    per = heads // groups
    shares = []
    for g in range(groups):
        x_at = np.arange(g * per * p, (g + 1) * per * p)      # in x, z, Y
        h_at = np.arange(g * per, (g + 1) * per)
        s_at = np.arange(g * n, (g + 1) * n)                  # in B, in C
        conv = np.concatenate([x_at, inner + s_at, inner + bc + s_at])
        cols = np.concatenate([x_at, inner + conv,
                               2 * inner + 2 * bc + h_at])
        shares.append({
            "in_kernel": whole["in_kernel"][:, cols],
            "conv_kernel": whole["conv_kernel"][:, conv],
            "conv_bias": whole["conv_bias"][conv],
            "dt_bias": whole["dt_bias"][h_at], "A_log": whole["A_log"][h_at],
            "D": whole["D"][h_at],
            "out_norm": {"scale": whole["out_norm"]["scale"][x_at]},
            "out_kernel": whole["out_kernel"][x_at]})
    return whole, shares


def test_the_head_shares_of_a_mamba_layer_add_up_to_the_uncut_layer():
    whole, shares = mamba_whole_and_shares()
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 40, 32))
    reference = lambda base: jax.jit(
        lambda p: plain.mamba2(NX, x, p, sizes(base)))
    want = reference(WHOLE)(whole)
    mixer = nemotron_h.Mamba2Mixer(2, 8, 1, 16, 4, 1e-5, chunk=16)
    share = jax.jit(lambda p: mixer.apply({"params": p}, x))
    parts = [share(p) for p in shares]
    np.testing.assert_allclose(sum(parts), want, atol=2e-5)
    # and a share is the reference given the same share
    np.testing.assert_allclose(parts[1], reference(SHARE)(shares[1]),
                               atol=2e-5)
    assert float(jnp.max(jnp.abs(parts[0]))) > 1e-2
    # the whole layer at once through the program, groups and all
    all_at_once = nemotron_h.Mamba2Mixer(8, 8, 4, 16, 4, 1e-5, chunk=16)
    np.testing.assert_allclose(
        jax.jit(lambda p: all_at_once.apply({"params": p}, x))(whole), want,
        atol=2e-5)


def test_the_head_shares_of_an_attention_layer_add_up_to_the_uncut_layer():
    heads, kv, d, hidden = 8, 2, 16, 32
    whole = seeded({"q_kernel": (hidden, heads * d),
                    "k_kernel": (hidden, kv * d),
                    "v_kernel": (hidden, kv * d),
                    "out_kernel": (heads * d, hidden)}, seed=6)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 40, hidden))
    reference = jax.jit(lambda x_, **kw: plain.attention(
        NX, x_, whole, sizes(WHOLE), **kw), static_argnames="query_block")
    want = reference(x, query_block=16)
    mixer = nemotron_h.AttentionMixer(2, 1, d)
    apply = jax.jit(lambda p: mixer.apply({"params": p}, x))
    total = 0.0
    for share in range(4):      # query heads 2s, 2s + 1 read kv head s // 2
        q_at = np.arange(2 * share * d, 2 * (share + 1) * d)
        kv_at = np.arange((share // 2) * d, (share // 2 + 1) * d)
        total = total + apply({
            "q_kernel": whole["q_kernel"][:, q_at],
            "k_kernel": whole["k_kernel"][:, kv_at],
            "v_kernel": whole["v_kernel"][:, kv_at],
            "out_kernel": whole["out_kernel"][q_at]})
    np.testing.assert_allclose(total, want, atol=2e-5)
    # no position signal: the last token's output does not care where the
    # earlier tokens sit
    swapped = x.at[:, [3, 17]].set(x[:, [17, 3]])
    np.testing.assert_allclose(reference(swapped)[:, -1], want[:, -1],
                               atol=2e-5)


def test_the_expert_shares_of_a_latent_layer_add_up_to_the_uncut_layer():
    """Each share's routed part goes through W_up; the shared expert,
    which every chip computes alike, is counted once."""
    hidden, latent, f, wide, experts = 32, 16, 24, 40, 16
    whole = seeded({"router_kernel": (hidden, experts),
                    "shared_up_kernel": (hidden, wide),
                    "shared_down_kernel": (wide, hidden),
                    "latent_down_kernel": (hidden, latent),
                    "latent_up_kernel": (latent, hidden),
                    "experts_up_kernel": (experts, latent, f),
                    "experts_down_kernel": (experts, f, latent)}, seed=8)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 24, hidden))
    want = jax.jit(lambda p: plain.latent_moe(NX, x, p, sizes(WHOLE)))(whole)
    once = jax.jit(lambda p: plain.shared(NX, x, p))(whole)
    arrived = checks.expert_shares_add_up(
        lambda lo: decoder.HeldExpertsLayer(
            experts, 4, lo, 6, f, 5.0, rows=8, pool=64, gated=False,
            latent=latent, shared_width=wide),
        lambda part, lo: once + jax.jit(lambda p: plain.routed(
            NX, x, p, sizes(WHOLE, expert_offset=lo)))(part),
        whole, x, want, once)
    assert arrived == 2 * 24 * 6        # every assignment fell on one share


def test_the_new_scopes_reach_the_compiled_step(built):
    """`ssd/proj`, `ssd/scan`, `moe/latent` and the attention layer's
    `gqa/proj`, `gqa/global` tag ops of the lowered loss, forward and
    backward."""
    text = built.lowered.as_text(debug_info=True)
    for scope in ("ssd/proj", "ssd/scan", "moe/latent", "moe/route",
                  "moe/shared", "moe/experts", "moe/dispatch", "moe/plan",
                  "gqa/proj",
                  "gqa/global", "attn/core", "lm/loss"):
        assert scope + "/" in text, scope
    from geomx_tpu.telemetry.layers import layer_of
    assert layer_of("ssd/scan") == "kernels"
    assert layer_of("ssd/proj") == layer_of("moe/latent") == "step program"
