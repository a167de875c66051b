"""Family `mellum`: the repo's `MellumLM` (pre-norm blocks of grouped-query
attention with rotary on every layer under a table per layer kind, plain
in the window layers and YaRN's in the global ones, no gate; a softmax
router and expert layers that hold some of their experts and have no
shared one; a next-token loss the model brings itself) under a
configuration's widths.  The program is imported here, at the top: a
checkout without the decoder fails at this import, at once.

It defines neither `attention_shape` nor `latent_attention_shape`: those
switch on readers whose FLOP counts are BERT's and MLA's."""
from __future__ import annotations

import numpy as np

from geomx_tpu.models.mellum import MellumConfig, MellumLM


def layer_kinds(config: dict) -> tuple:
    """((mixer, ffn), ...) of the layers kept, by their 0-based indices in
    `layer_types` and `mlp_layer_types`: a window or a global layer, and an
    expert layer ("moe"), which is what every entry of the published list
    is ("sparse"; `intermediate_size`, a dense MLP's width, is used by no
    layer)."""
    kinds = []
    for index in config["kept_layers"]:
        mixer = {"sliding_attention": "window", "full_attention": "global"}[
            config["layer_types"][index]]
        kinds.append((mixer, {"sparse": "moe"}[
            config["mlp_layer_types"][index]]))
    if len(kinds) != config["num_hidden_layers"]:
        raise ValueError("kept_layers and num_hidden_layers disagree")
    return tuple(kinds)


def yarn(config: dict) -> dict:
    """The full layers' `rope_parameters` under the names model and
    reference use."""
    full = config["rope_parameters"]["full_attention"]
    if full["rope_type"] != "yarn":
        raise ValueError("the full layers' positions are YaRN's")
    return dict(theta=float(full["rope_theta"]), factor=float(full["factor"]),
                original=full["original_max_position_embeddings"],
                beta_fast=float(full["beta_fast"]),
                beta_slow=float(full["beta_slow"]),
                attention_factor=full["attention_factor"])


def sizes(config: dict) -> dict:
    """The configuration's keys under the names model and reference use."""
    ropes = config["rope_parameters"]
    if ropes["sliding_attention"]["rope_type"] != "default":
        raise ValueError("the window layers' positions are plain rotary")
    if not config["norm_topk_prob"]:
        raise ValueError("the picked probabilities are renormalised here")
    return dict(
        vocab=config["vocab_size"], hidden=config["hidden_size"],
        layers=layer_kinds(config), num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], window=config["sliding_window"],
        rope_theta=float(ropes["sliding_attention"]["rope_theta"]),
        yarn=yarn(config), expert_width=config["moe_intermediate_size"],
        num_experts=config["router_experts"],
        experts_held=config["num_experts"],
        expert_offset=config["expert_offset"],
        top_k=config["num_experts_per_tok"], eps=config["rms_norm_eps"])


def build_model(config: dict):
    import jax.numpy as jnp
    from geomx_tpu.ops.gqa_elementwise import Yarn
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        config["precision"]]
    run = config.get("program", {})
    s = sizes(config)
    return MellumLM(MellumConfig(
        **{**s, "yarn": Yarn(**s["yarn"])}, loss_block=run.get("loss_block_tokens", 2048),
        expert_rows=run.get("expert_block_rows", 512),
        expert_pool=run.get("expert_pool_places"),
        remat=run.get("remat_each_layer", True)), dtype=dtype)


def make_data(config: dict, rng: np.random.Generator, rows: int):
    """Seeded tokens, uniform over the vocabulary's slice; `y` is the next
    token, `[rows, L]` like `x`."""
    t = rng.integers(0, config["vocab_size"],
                     (rows, config["sequence_length"] + 1), dtype=np.int32)
    return t[:, :-1], t[:, 1:]


# unit entries: a token's own state, not its neighbours', is most of what a
# router reads (`weight_std`)
EMBEDDING_STD = 1.0


def weight_std(path, shape) -> float:
    """Fan-in for every matrix, so that a layer writes entries of at most
    unit size into the residual stream, and an embedding of unit entries,
    so that the stream in front of every router is mostly the token's own
    state: the seeded router then sends this chip what an even one would
    (30,881-33,570 assignments a layer for 32,768 over six seeds, an
    expert 1,544-2,470 for 2,048), as a router trained with a balancing
    term does.  With the other decoders' 0.02 (and with 0.1) the stream
    behind the first attention layer is what the layers wrote, a band's
    or the whole prefix's average that neighbouring tokens share, whole
    stretches of the sequence go to the same experts, and the held load
    swings 21,224-43,881 a layer with the seed (PERF.md section 6, PR
    40)."""
    if path[-1] == "embedding":
        return EMBEDDING_STD
    return float(shape[-2]) ** -0.5


def seen_pairs(length: int, window: int | None) -> int:
    """(query, key) pairs of one sequence and head that hold a score:
    causal, and inside a band of `window` keys where one is given."""
    if window is None or window >= length:
        return length * (length + 1) // 2
    return window * (window + 1) // 2 + (length - window) * window


def forward_flops_per_token(config: dict) -> float:
    """Matmul FLOPs of one forward pass of one token at the cell's
    sequence length, from shapes, 2 a multiply-add.  Attention: the four
    projections, and Q K^T and P V over the pairs a layer sees (the band's
    in a window layer, the causal half in a global one) averaged over the
    sequence's tokens.  Expert layer: the router over all the experts and
    the routed experts a token reaches here on average under even routing
    (held x top-k / routed: 2 at 16 of 64 and top 8); no shared expert.
    Head over the vocabulary's slice.  Norms, rotary, the experts'
    nonlinearity and softmax are not counted."""
    s = sizes(config)
    d, length = s["hidden"], config["sequence_length"]
    wide, narrow = s["num_heads"] * s["head_dim"], \
        s["num_kv_heads"] * s["head_dim"]
    proj = 2.0 * d * (2 * wide + 2 * narrow)
    core = lambda window: (4.0 * s["head_dim"] * s["num_heads"]
                           * seen_pairs(length, window) / length)
    reached = s["experts_held"] * s["top_k"] / s["num_experts"]
    moe = 2.0 * d * s["num_experts"] + 6.0 * d * s["expert_width"] * reached
    total = 2.0 * d * s["vocab"]
    for mixer, _ in s["layers"]:
        total += proj + core(s["window"] if mixer == "window" else None) + moe
    return total


def train_flops_per_sample(config: dict) -> float:
    """A sample is one sequence.  Forward plus backward (twice the
    forward), no recomputation."""
    return 3.0 * config["sequence_length"] * forward_flops_per_token(config)


def _attention_shape(config: dict, mixer: str) -> dict:
    s = sizes(config)
    length = config["sequence_length"]
    return {"batch": config["per_chip_batch"], "heads": s["num_heads"],
            "kv_heads": s["num_kv_heads"], "length": length,
            "qk_dim": s["head_dim"], "v_dim": s["head_dim"],
            "pairs": seen_pairs(
                length, s["window"] if mixer == "window" else None),
            "layers": sum(m == mixer for m, _ in s["layers"])}


def window_attention_shape(config: dict) -> dict:
    """What the window layers' attention sees in a step; `pairs`: W (W +
    1) / 2 + (L - W) W a sequence and head."""
    return _attention_shape(config, "window")


def global_attention_shape(config: dict) -> dict:
    """What the global layers' attention sees in a step; `pairs`: L (L +
    1) / 2 a sequence and head."""
    return _attention_shape(config, "global")


def window_attention_flops_per_step(shape: dict) -> float:
    """Forward Q K^T (2 e_qk) and P V (2 e_v) a seen pair and query head;
    backward dV, dP (2 e_v each), dQ, dK (2 e_qk each): 6 (e_qk + e_v) =
    1,536 at 128.  The backward's recomputation of the scores and the
    rematerialised forward are the program's own cost and are not
    counted."""
    return (6.0 * (shape["qk_dim"] + shape["v_dim"]) * shape["pairs"]
            * shape["batch"] * shape["heads"] * shape["layers"])


global_attention_flops_per_step = window_attention_flops_per_step


def reference_loss(config: dict, nx):
    from benchmark.references import mellum
    s = sizes(config)
    return lambda params, x, y: mellum.loss(params, x, y, s, nx)
