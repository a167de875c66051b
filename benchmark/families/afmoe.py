"""Family `afmoe`: the repo's `AfmoeLM` (gated grouped-query attention in
window and global layers, a dense SwiGLU layer and expert layers that hold
some of their experts, four norms a block, a next-token loss the model
brings itself) under a configuration's widths.  The program is imported
here, at the top: a checkout without the decoder fails at this import, at
once.

It defines neither `attention_shape` nor `latent_attention_shape`: those
switch on readers whose FLOP counts are BERT's and MLA's."""
from __future__ import annotations

import math

import numpy as np

from geomx_tpu.models.afmoe import AfmoeConfig, AfmoeLM


def layer_kinds(config: dict) -> tuple:
    """((mixer, ffn), ...) of the layers kept, by their 0-based indices in
    `layer_types`: a window or a global layer as the list says, a dense
    MLP in the first `num_dense_layers` layers."""
    kinds = []
    for index in config["kept_layers"]:
        mixer = {"sliding_attention": "window", "full_attention": "global"}[
            config["layer_types"][index]]
        kinds.append((mixer, "mlp" if index < config["num_dense_layers"]
                      else "moe"))
    if len(kinds) != config["num_hidden_layers"]:
        raise ValueError("kept_layers and num_hidden_layers disagree")
    return tuple(kinds)


def sizes(config: dict) -> dict:
    """The configuration's keys under the names model and reference use."""
    return dict(
        vocab=config["vocab_size"], hidden=config["hidden_size"],
        layers=layer_kinds(config), num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], window=config["sliding_window"],
        rope_theta=float(config["rope_theta"]),
        dense_width=config["intermediate_size"],
        expert_width=config["moe_intermediate_size"],
        num_experts=config["router_experts"],
        experts_held=config["num_experts"],
        expert_offset=config["expert_offset"],
        top_k=config["num_experts_per_tok"],
        routed_scaling=config["route_scale"],
        shared_experts=config["num_shared_experts"],
        embedding_scale=(math.sqrt(config["hidden_size"])
                         if config["mup_enabled"] else 1.0),
        eps=config["rms_norm_eps"])


def build_model(config: dict):
    import jax.numpy as jnp
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        config["precision"]]
    run = config.get("program", {})
    return AfmoeLM(AfmoeConfig(
        **sizes(config), loss_block=run.get("loss_block_tokens", 2048),
        expert_rows=run.get("expert_block_rows", 512),
        expert_pool=run.get("expert_pool_places"),
        remat=run.get("remat_each_layer", True)), dtype=dtype)


def make_data(config: dict, rng: np.random.Generator, rows: int):
    """Seeded tokens, uniform over the vocabulary's slice; `y` is the next
    token, `[rows, L]` like `x`."""
    t = rng.integers(0, config["vocab_size"],
                     (rows, config["sequence_length"] + 1), dtype=np.int32)
    return t[:, :-1], t[:, 1:]


def weight_std(path, shape) -> float:
    if path[-1] == "embedding":
        return 0.02
    return float(shape[-2]) ** -0.5          # fan-in of every matrix here


def seen_pairs(length: int, window: int | None) -> int:
    """(query, key) pairs of one sequence and head that hold a score:
    causal, and inside a band of `window` keys where one is given."""
    if window is None or window >= length:
        return length * (length + 1) // 2
    return window * (window + 1) // 2 + (length - window) * window


def forward_flops_per_token(config: dict) -> float:
    """Matmul FLOPs of one forward pass of one token at the cell's
    sequence length, from shapes, 2 a multiply-add.  Attention: the five
    projections, and Q K^T and P V over the pairs a layer sees (the band's
    in a window layer, the causal half in a global one) averaged over the
    sequence's tokens.  Expert layer: router, shared expert, and the
    routed experts a token reaches here on average under even routing
    (held x top-k / routed).  Head over the vocabulary's slice.  Norms,
    rotary, gates' nonlinearities and softmax are not counted."""
    s = sizes(config)
    d, length = s["hidden"], config["sequence_length"]
    wide, narrow = s["num_heads"] * s["head_dim"], \
        s["num_kv_heads"] * s["head_dim"]
    proj = 2.0 * d * (3 * wide + 2 * narrow)
    core = lambda window: (4.0 * s["head_dim"] * s["num_heads"]
                           * seen_pairs(length, window) / length)
    mlp = 6.0 * d * s["dense_width"]
    reached = s["experts_held"] * s["top_k"] / s["num_experts"]
    moe = 2.0 * d * s["num_experts"] + 6.0 * d * s["expert_width"] * (
        s["shared_experts"] + reached)
    total = 2.0 * d * s["vocab"]
    for mixer, ffn in s["layers"]:
        total += proj + core(s["window"] if mixer == "window" else None) + (
            mlp if ffn == "mlp" else moe)
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
    from benchmark.references import afmoe
    s = sizes(config)
    return lambda params, x, y: afmoe.loss(params, x, y, s, nx)
