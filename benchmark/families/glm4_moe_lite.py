"""Family `glm4_moe_lite`: the repo's `Glm4MoeLiteLM` (pre-norm blocks of
latent attention with a low-rank query and decoupled rotary on every
layer, a dense SwiGLU lead, expert layers that hold some of their experts
beside one shared expert, a sigmoid router, and a multi-token-prediction
module that shares embedding and head; the model brings the weighted sum
of its two losses itself) under a configuration's widths.  The program is
imported here, at the top: a checkout without the decoder fails at this
import, at once."""
from __future__ import annotations

import numpy as np

from geomx_tpu.models.glm4_moe_lite import Glm4MoeLiteConfig, Glm4MoeLiteLM


def layer_kinds(config: dict) -> tuple:
    """((mixer, ffn), ...) of the layers kept, by their published 0-based
    indices: latent attention everywhere, a dense MLP in the first
    `first_k_dense_replace` layers, an expert layer behind them.  The
    multi-token-prediction module (published as layer
    `published.num_hidden_layers`) is not among them: `mtp_depth` counts
    it."""
    kinds = tuple(("mla", "mlp" if index < config["first_k_dense_replace"]
                   else "moe") for index in config["kept_layers"])
    if len(kinds) != config["num_hidden_layers"]:
        raise ValueError("kept_layers and num_hidden_layers disagree")
    return kinds


def sizes(config: dict) -> dict:
    """The configuration's keys under the names model and reference use."""
    if config["rope_scaling"] is not None or config["partial_rotary_factor"] \
            != 1:
        raise ValueError("plain rotary over the whole rope part here")
    if (config["n_group"], config["topk_group"]) != (1, 1):
        raise ValueError("one expert group here: a plain top-k")
    if not config["norm_topk_prob"]:
        raise ValueError("the picked scores are renormalised here")
    if config["num_experts"] != config["n_routed_experts"]:
        raise ValueError("num_experts repeats the held n_routed_experts")
    return dict(
        vocab=config["vocab_size"], hidden=config["hidden_size"],
        layers=layer_kinds(config), num_heads=config["num_attention_heads"],
        q_rank=config["q_lora_rank"], kv_rank=config["kv_lora_rank"],
        qk_nope_dim=config["qk_nope_head_dim"],
        qk_rope_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        rope_theta=float(config["rope_theta"]),
        dense_width=config["intermediate_size"],
        expert_width=config["moe_intermediate_size"],
        num_experts=config["router_experts"],
        experts_held=config["n_routed_experts"],
        expert_offset=config["expert_offset"],
        top_k=config["num_experts_per_tok"],
        routed_scaling=config["routed_scaling_factor"],
        shared_experts=config["n_shared_experts"],
        mtp_depth=config["num_nextn_predict_layers"],
        mtp_weight=config["mtp_loss_weight"], mtp_block=("mla", "moe"),
        eps=config["rms_norm_eps"])


def build_model(config: dict):
    import jax.numpy as jnp
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        config["precision"]]
    run = config.get("program", {})
    return Glm4MoeLiteLM(Glm4MoeLiteConfig(
        **sizes(config), loss_block=run.get("loss_block_tokens", 2048),
        expert_rows=run.get("expert_block_rows", 512),
        expert_pool=run.get("expert_pool_places"),
        remat=run.get("remat_each_layer", True)), dtype=dtype)


def make_data(config: dict, rng: np.random.Generator, rows: int):
    """Seeded tokens, uniform over the vocabulary's slice; `y` is the next
    token, `[rows, L]` like `x`: the module's second-next token is `y`
    shifted by one, no further input."""
    t = rng.integers(0, config["vocab_size"],
                     (rows, config["sequence_length"] + 1), dtype=np.int32)
    return t[:, :-1], t[:, 1:]


# unit entries: a token's own state, not its neighbours', is most of what a
# router reads (`weight_std`)
EMBEDDING_STD = 1.0


def weight_std(path, shape) -> float:
    """Fan-in for every matrix and an embedding of unit entries, Mellum's
    seeding and for its reason: the stream in front of every router, the
    module's among them (it reads `W_eh` of two unit-RMS halves, one of
    them the next token's own embedding), is mostly the token's own state,
    so the seeded routers send this chip what even ones would (PERF.md
    section 6, PR 45 has the held load by seed and layer)."""
    if path[-1] == "embedding":
        return EMBEDDING_STD
    return float(shape[-2]) ** -0.5


def _mixer_flops_per_token(s: dict) -> float:
    """The latent mixer's products outside the core, 2 a multiply-add: the
    query down and up, keys and values down and up, the product out."""
    d, heads = s["hidden"], s["num_heads"]
    qk = s["qk_nope_dim"] + s["qk_rope_dim"]
    return 2.0 * (d * s["q_rank"] + s["q_rank"] * heads * qk
                  + d * (s["kv_rank"] + s["qk_rope_dim"])
                  + s["kv_rank"] * heads * (s["qk_nope_dim"] + s["v_head_dim"])
                  + heads * s["v_head_dim"] * d)


def forward_flops_per_token(config: dict) -> float:
    """Matmul FLOPs of one forward pass of one token at the cell's
    sequence length, from shapes, 2 a multiply-add.  Latent attention: its
    five projections and the causal half of Q K^T and P V.  Dense layer:
    the SwiGLU MLP.  Expert layer: the router over all the experts, the
    shared expert, and the routed experts a token reaches here on average
    under even routing (held x top-k / routed: 0.5 at 8 of 64 and top 4).
    Head over the vocabulary's slice.  The multi-token-prediction module:
    its joining matrix, its block (latent attention + expert layer) and a
    second pass over the head.  Norms, rotary, the experts' nonlinearity
    and softmax are not counted."""
    s = sizes(config)
    d, length = s["hidden"], config["sequence_length"]
    qk = s["qk_nope_dim"] + s["qk_rope_dim"]
    mla = _mixer_flops_per_token(s) \
        + (qk + s["v_head_dim"]) * length * s["num_heads"]
    mlp = 6.0 * d * s["dense_width"]
    reached = s["experts_held"] * s["top_k"] / s["num_experts"]
    moe = 2.0 * d * s["num_experts"] + 6.0 * d * s["expert_width"] * (
        s["shared_experts"] + reached)
    head = 2.0 * d * s["vocab"]
    total = head
    for _, ffn in s["layers"]:
        total += mla + (mlp if ffn == "mlp" else moe)
    total += s["mtp_depth"] * (2.0 * 2 * d * d + mla + moe + head)
    return total


def train_flops_per_sample(config: dict) -> float:
    """A sample is one sequence.  Forward plus backward (twice the
    forward), no recomputation."""
    return 3.0 * config["sequence_length"] * forward_flops_per_token(config)


def latent_attention_shape(config: dict) -> dict:
    """What one call of the attention kernel sees, per latent layer; the
    module's block is one of them."""
    s = sizes(config)
    return {"batch": config["per_chip_batch"], "heads": s["num_heads"],
            "length": config["sequence_length"],
            "qk_dim": s["qk_nope_dim"] + s["qk_rope_dim"],
            "v_dim": s["v_head_dim"],
            "layers": len(s["layers"]) + s["mtp_depth"]}


def latent_attention_flops_per_step(shape: dict) -> float:
    """Causal: half of L^2 pairs.  Forward Q K^T (2 e_qk) and P V (2 e_v);
    backward dV, dP (2 e_v each), dQ, dK (2 e_qk each): (3 e_qk + 3 e_v)
    B H L^2 = 1,536 B H L^2 at 256 and 256.  The backward's recomputation
    of the scores and the rematerialised forward are the program's own
    cost and are not counted."""
    return (3.0 * (shape["qk_dim"] + shape["v_dim"]) * shape["batch"]
            * shape["heads"] * shape["length"] ** 2 * shape["layers"])


def reference_loss(config: dict, nx):
    from benchmark.references import glm4_moe_lite
    s = sizes(config)
    return lambda params, x, y: glm4_moe_lite.loss(params, x, y, s, nx)
