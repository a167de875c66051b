"""Family `kimi_linear`: the repo's `KimiLinearLM` (KDA and latent-
attention mixers, a dense SwiGLU layer and expert layers that hold some of
their experts, a next-token loss the model brings itself) under a
configuration's widths.  The program is imported here, at the top: a
checkout without the decoder fails at this import, at once."""
from __future__ import annotations

import numpy as np

from geomx_tpu.models.kimi_linear import KimiLinearConfig, KimiLinearLM


def layer_kinds(config: dict) -> tuple:
    """((mixer, ffn), ...) of the layers kept, by their published indices
    (1-based): KDA or latent attention as `linear_attn_config` lists them,
    a dense MLP in the first `first_k_dense_replace` layers."""
    linear = config["linear_attn_config"]
    kinds = []
    for index in config["kept_layers"]:
        if index in linear["kda_layers"]:
            mixer = "kda"
        elif index in linear["full_attn_layers"]:
            mixer = "mla"
        else:
            raise ValueError(f"layer {index} is in neither list")
        kinds.append((mixer, "mlp" if index <= config["first_k_dense_replace"]
                      else "moe"))
    if len(kinds) != config["num_hidden_layers"]:
        raise ValueError("kept_layers and num_hidden_layers disagree")
    return tuple(kinds)


def sizes(config: dict) -> dict:
    """The configuration's keys under the names model and reference use."""
    linear = config["linear_attn_config"]
    if linear["num_heads"] != config["num_attention_heads"]:
        raise ValueError("one head count serves both mixers here")
    return dict(
        vocab=config["vocab_size"], hidden=config["hidden_size"],
        layers=layer_kinds(config), num_heads=config["num_attention_heads"],
        kda_head_dim=linear["head_dim"],
        conv_size=linear["short_conv_kernel_size"],
        qk_nope_dim=config["qk_nope_head_dim"],
        qk_rope_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"], kv_rank=config["kv_lora_rank"],
        dense_width=config["intermediate_size"],
        expert_width=config["moe_intermediate_size"],
        num_experts=config["router_experts"],
        experts_held=config["num_experts"],
        expert_offset=config["expert_offset"],
        top_k=config["num_experts_per_token"],
        routed_scaling=config["routed_scaling_factor"],
        shared_experts=config["num_shared_experts"],
        eps=config["rms_norm_eps"])


def build_model(config: dict):
    import jax.numpy as jnp
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        config["precision"]]
    run = config.get("program", {})
    return KimiLinearLM(KimiLinearConfig(
        **sizes(config), kda_chunk=run.get("kda_chunk", 64),
        kda_sub=run.get("kda_sub_block", 16),
        loss_block=run.get("loss_block_tokens", 2048),
        expert_rows=run.get("expert_block_rows", 512),
        remat=run.get("remat_each_layer", True)), dtype=dtype)


def make_data(config: dict, rng: np.random.Generator, rows: int):
    """Seeded tokens, uniform over the vocabulary's slice; `y` is the next
    token, `[rows, L]` like `x`."""
    t = rng.integers(0, config["vocab_size"],
                     (rows, config["sequence_length"] + 1), dtype=np.int32)
    return t[:, :-1], t[:, 1:]


def weight_std(path, shape) -> float:
    name = path[-1]
    if name == "embedding":
        return 0.02
    if name.endswith("_conv") or name == "A_log":
        return 0.5
    if name == "dt_bias":
        return 1.0
    return float(shape[-2]) ** -0.5          # fan-in of every matrix here


def forward_flops_per_token(config: dict) -> float:
    """Matmul FLOPs of one forward pass of one token at the cell's
    sequence length, from shapes, 2 a multiply-add.  KDA: its projections
    and the recurrence's 7 d_k d_v a head (decay, read, write, output;
    the chunked form's extra products are not model FLOPs).  Latent
    attention: projections and the causal half of Q K^T and P V.  Expert
    layer: router, shared expert, and the routed experts a token reaches
    here on average under even routing (held x top-k / routed).  Head over
    the vocabulary's slice.  Norms, gates' nonlinearities, the short
    convolutions and softmax are not counted."""
    s = sizes(config)
    d, heads, length = s["hidden"], s["num_heads"], config["sequence_length"]
    kd = s["kda_head_dim"]
    width = heads * kd
    kda = 2.0 * (3 * d * width + 2 * (d * kd + kd * width) + d * heads
                 + width * d) + 7.0 * heads * kd * kd
    qk = s["qk_nope_dim"] + s["qk_rope_dim"]
    mla = 2.0 * (d * heads * qk + d * (s["kv_rank"] + s["qk_rope_dim"])
                 + s["kv_rank"] * heads * (s["qk_nope_dim"] + s["v_head_dim"])
                 + heads * s["v_head_dim"] * d) \
        + (qk + s["v_head_dim"]) * length * heads
    mlp = 6.0 * d * s["dense_width"]
    reached = s["experts_held"] * s["top_k"] / s["num_experts"]
    moe = 2.0 * d * s["num_experts"] + 6.0 * d * s["expert_width"] * (
        s["shared_experts"] + reached)
    total = 2.0 * d * s["vocab"]
    for mixer, ffn in s["layers"]:
        total += (kda if mixer == "kda" else mla) + (
            mlp if ffn == "mlp" else moe)
    return total


def train_flops_per_sample(config: dict) -> float:
    """A sample is one sequence.  Forward plus backward (twice the
    forward), no recomputation."""
    return 3.0 * config["sequence_length"] * forward_flops_per_token(config)


def kda_scan_shape(config: dict) -> dict:
    """What the KDA recurrence sees in a step."""
    s = sizes(config)
    return {"tokens": config["per_chip_batch"] * config["sequence_length"],
            "heads": s["num_heads"], "key_dim": s["kda_head_dim"],
            "value_dim": s["kda_head_dim"],
            "layers": sum(m == "kda" for m, _ in s["layers"])}


def kda_scan_flops_per_step(shape: dict) -> float:
    """The recurrence's own 7 d_k d_v a token and head forward (decay the
    state, read it at k, write the outer product, read it at q) and twice
    that backward."""
    return (21.0 * shape["key_dim"] * shape["value_dim"] * shape["tokens"]
            * shape["heads"] * shape["layers"])


def kda_scan_bytes_per_step(shape: dict) -> float:
    """The least HBM traffic: forward reads q, k, v (2 B an element), g
    (4 B) and writes o (2 B); backward reads those and do again and writes
    dq, dk, dv (2 B) and dg (4 B); the state never leaves the chip's fast
    memory.  beta is 1/128 of these and left out."""
    dk, dv = shape["key_dim"], shape["value_dim"]
    forward = 2 * 2 * dk + 4 * dk + 2 * 2 * dv
    backward = forward + 2 * 2 * dk + 4 * dk + 2 * dv
    return (float(forward + backward) * shape["tokens"] * shape["heads"]
            * shape["layers"])


def latent_attention_shape(config: dict) -> dict:
    """What one call of the attention kernel sees, per latent layer."""
    s = sizes(config)
    return {"batch": config["per_chip_batch"], "heads": s["num_heads"],
            "length": config["sequence_length"],
            "qk_dim": s["qk_nope_dim"] + s["qk_rope_dim"],
            "v_dim": s["v_head_dim"],
            "layers": sum(m == "mla" for m, _ in s["layers"])}


def latent_attention_flops_per_step(shape: dict) -> float:
    """Causal: half of L^2 pairs.  Forward Q K^T (2 e_qk) and P V (2 e_v);
    backward dV, dP (2 e_v each), dQ, dK (2 e_qk each): (3 e_qk + 3 e_v)
    B H L^2 = 960 B H L^2 at 192 and 128.  The backward's recomputation of
    the scores is the kernel's own cost and is not counted."""
    return (3.0 * (shape["qk_dim"] + shape["v_dim"]) * shape["batch"]
            * shape["heads"] * shape["length"] ** 2 * shape["layers"])


def reference_loss(config: dict, nx):
    from benchmark.references import kimi_linear
    s = sizes(config)
    return lambda params, x, y: kimi_linear.loss(params, x, y, s, nx)
