"""Family `ouro`: the repo's `OuroLM` (a stack of dense sandwich-norm
blocks, full causal attention with rotary and no q/k norm on every layer,
applied `total_ut_steps` times to the same stream with the same
parameters, the head read and an exit gate asked after every pass; the
model brings the expected-exit loss with its entropy term itself) under a
configuration's widths.  The program is imported here, at the top: a
checkout without the looped decoder fails at this import, at once.

It defines neither `window_attention_shape`, `attention_shape` nor
`latent_attention_shape`: each switches on readers whose counts are
another mask's."""
from __future__ import annotations

import numpy as np

from geomx_tpu.models.ouro import OuroConfig, OuroLM


def layer_kinds(config: dict) -> tuple:
    """((mixer, ffn), ...) of the layers kept, by their published 0-based
    indices in `layer_types`: full attention and a dense MLP, every one."""
    kinds = tuple(({"full_attention": "global"}[config["layer_types"][index]],
                   "mlp") for index in config["kept_layers"])
    if len(kinds) != config["num_hidden_layers"]:
        raise ValueError("kept_layers and num_hidden_layers disagree")
    return kinds


def sizes(config: dict) -> dict:
    """The configuration's keys under the names model and reference use."""
    if config["rope_scaling"] is not None:
        raise ValueError("plain rotary over the whole head here")
    if config["use_sliding_window"] or config["sliding_window"] is not None:
        raise ValueError("every layer sees every earlier key here: no window")
    if config["hidden_act"] != "silu" or config["tie_word_embeddings"]:
        raise ValueError("a SwiGLU MLP and an untied head here")
    return dict(
        vocab=config["vocab_size"], hidden=config["hidden_size"],
        layers=layer_kinds(config), num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], rope_theta=float(config["rope_theta"]),
        dense_width=config["intermediate_size"],
        loops=config["total_ut_steps"], exit_beta=config["exit_beta"],
        eps=config["rms_norm_eps"])


def build_model(config: dict):
    import jax.numpy as jnp
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        config["precision"]]
    run = config.get("program", {})
    return OuroLM(OuroConfig(
        **sizes(config), loss_block=run.get("loss_block_tokens", 2048),
        remat=run.get("remat_each_layer", True)), dtype=dtype)


def make_data(config: dict, rng: np.random.Generator, rows: int):
    """Seeded tokens, uniform over the whole vocabulary; `y` is the next
    token, `[rows, L]` like `x`: every loop step is held to it."""
    t = rng.integers(0, config["vocab_size"],
                     (rows, config["sequence_length"] + 1), dtype=np.int32)
    return t[:, :-1], t[:, 1:]


# unit entries: the first pass then reads a stream of the scale the final
# norm gives the other three (`weight_std`)
EMBEDDING_STD = 1.0


def weight_std(path, shape) -> float:
    """Fan-in for every matrix (the exit gate's `kernel` [hidden, 1] among
    them, so that its logits are near N(0, 1) on a normed stream and the
    loop steps all hold mass), its `bias` zero, and an embedding of unit
    entries (Mellum's): passes 2..T read what the final norm wrote, unit
    RMS, and with unit entries pass 1 reads the same scale."""
    if path[-1] == "embedding":
        return EMBEDDING_STD
    if path[-1] == "bias":
        return 0.0
    return float(shape[-2]) ** -0.5


def forward_flops_per_token(config: dict) -> float:
    """Matmul FLOPs of one forward pass of one token at the cell's
    sequence length, from shapes, 2 a multiply-add, **the stack and the
    head counted `total_ut_steps` times**: a step applies every layer that
    often and reads the head after each pass.  A layer's application: the
    four projections, Q K^T and P V over the causal half averaged over the
    sequence's tokens, the SwiGLU MLP.  The exit gate's product (2 x hidden
    a pass) is counted, norms, rotary, softmax and the exit distribution are
    not."""
    s = sizes(config)
    d, length = s["hidden"], config["sequence_length"]
    wide, narrow = s["num_heads"] * s["head_dim"], \
        s["num_kv_heads"] * s["head_dim"]
    proj = 2.0 * d * (2 * wide + 2 * narrow)
    core = 4.0 * s["head_dim"] * s["num_heads"] * (length + 1) / 2
    mlp = 6.0 * d * s["dense_width"]
    one_pass = (len(s["layers"]) * (proj + core + mlp)
                + 2.0 * d * s["vocab"] + 2.0 * d)
    return s["loops"] * one_pass


def train_flops_per_sample(config: dict) -> float:
    """A sample is one sequence.  Forward plus backward (twice the
    forward), no recomputation."""
    return 3.0 * config["sequence_length"] * forward_flops_per_token(config)


def global_attention_shape(config: dict) -> dict:
    """What the attention cores see in a step; `layers` counts
    APPLICATIONS (layers x loop steps: each is a call of the kernels),
    `pairs`: L (L + 1) / 2 a sequence and head."""
    s = sizes(config)
    length = config["sequence_length"]
    return {"batch": config["per_chip_batch"], "heads": s["num_heads"],
            "kv_heads": s["num_kv_heads"], "length": length,
            "qk_dim": s["head_dim"], "v_dim": s["head_dim"],
            "pairs": length * (length + 1) // 2,
            "layers": len(s["layers"]) * s["loops"]}


def global_attention_flops_per_step(shape: dict) -> float:
    """Forward Q K^T (2 e_qk) and P V (2 e_v) a seen pair and query head;
    backward dV, dP (2 e_v each), dQ, dK (2 e_qk each): 6 (e_qk + e_v) =
    1,536 at 128.  The backward's recomputation of the scores and the
    rematerialised forward are the program's own cost and are not
    counted."""
    return (6.0 * (shape["qk_dim"] + shape["v_dim"]) * shape["pairs"]
            * shape["batch"] * shape["heads"] * shape["layers"])


def reference_loss(config: dict, nx):
    from benchmark.references import ouro
    s = sizes(config)
    return lambda params, x, y: ouro.loss(params, x, y, s, nx)
