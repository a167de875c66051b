"""Family `nemotron_h`: the repo's `NemotronHLM` (layers of ONE half each:
a Mamba-2 state-space mixer, plain grouped-query attention, or a LatentMoE
that holds some of its experts; a next-token loss the model brings itself)
under a configuration's widths and a chip's share of heads, groups,
experts and vocabulary.  The program is imported here, at the top: a
checkout without the decoder fails at this import, at once.

It defines none of `attention_shape`, `latent_attention_shape`,
`window_attention_shape`, `kda_scan_shape`: those switch on readers whose
FLOP counts are another family's."""
from __future__ import annotations

import numpy as np

from geomx_tpu.models.nemotron_h import NemotronHConfig, NemotronHLM

KINDS = {"M": ("mamba", None), "*": ("attention", None), "E": (None, "moe")}


def layer_kinds(config: dict) -> tuple:
    """((mixer, ffn), ...) of the layers kept, by their 0-based indices in
    `hybrid_override_pattern`, one of each pair None: a layer is a mixer
    ("M" Mamba-2, "*" attention) or a feed-forward ("E" LatentMoE) alone."""
    kinds = tuple(KINDS[config["hybrid_override_pattern"][index]]
                  for index in config["kept_layers"])
    if len(kinds) != config["num_hidden_layers"]:
        raise ValueError("kept_layers and num_hidden_layers disagree")
    return kinds


def sizes(config: dict) -> dict:
    """The configuration's keys under the names model and reference use;
    head, group and expert counts are what this chip holds."""
    return dict(
        vocab=config["vocab_size"], hidden=config["hidden_size"],
        layers=layer_kinds(config), mamba_heads=config["mamba_num_heads"],
        mamba_head_dim=config["mamba_head_dim"],
        mamba_groups=config["n_groups"], state_size=config["ssm_state_size"],
        conv_size=config["conv_kernel"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        expert_width=config["moe_intermediate_size"],
        shared_width=config["moe_shared_expert_intermediate_size"],
        latent=config["moe_latent_size"],
        num_experts=config["router_experts"],
        experts_held=config["n_routed_experts"],
        expert_offset=config["expert_offset"],
        top_k=config["num_experts_per_tok"],
        routed_scaling=float(config["routed_scaling_factor"]),
        shared_experts=config["n_shared_experts"],
        eps=config["layer_norm_epsilon"])


def build_model(config: dict):
    import jax.numpy as jnp
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        config["precision"]]
    run = config.get("program", {})
    return NemotronHLM(NemotronHConfig(
        **sizes(config), ssd_chunk=config["chunk_size"],
        loss_block=run.get("loss_block_tokens", 2048),
        expert_rows=run.get("expert_block_rows", 512),
        expert_pool=run.get("expert_pool_places"),
        remat=run.get("remat_each_layer", True)), dtype=dtype)


def make_data(config: dict, rng: np.random.Generator, rows: int):
    """Seeded tokens, uniform over the vocabulary's slice; `y` is the next
    token, `[rows, L]` like `x`."""
    t = rng.integers(0, config["vocab_size"],
                     (rows, config["sequence_length"] + 1), dtype=np.int32)
    return t[:, :-1], t[:, 1:]


# the published depth: a layer's share of the residual stream's variance
# (`rescale_prenorm_residual`: `weight_std`)
PUBLISHED_LAYERS = 88
# the leaves that are no fan-in matrix (the configuration's `assumed`)
STD = {"embedding": PUBLISHED_LAYERS ** 0.5, "conv_kernel": 0.5,
       "conv_bias": 0.2, "A_log": 0.5, "dt_bias": 1.0, "D": 0.25}


def weight_std(path, shape) -> float:
    """Fan-in for every matrix, so a layer writes unit entries into the
    residual stream; the embedding, which stands here for what the layers
    before the kept ones wrote, has entries of sqrt(88), so that a layer
    adds 1 / 88 of the stream's variance: what the published
    `rescale_prenorm_residual` asks of an initialisation at the published
    depth, said on the stream's side, which leaves every matrix at the
    scale where Adam's constant steps of 1e-5 are small against it.  The
    tokens of a batch then share about a hundredth of their normed state
    and the seeded router sends this chip what an even one would
    (4,500-6,500 assignments a layer for 5,632), as a router trained with
    its selection bias does; with an embedding of 0.02 a layer's
    squared-ReLU and averaging outputs are the stream, 4-17% of the normed
    state is the same for every token, and the held experts' load swings
    4,500-8,400 a layer with the seed (PERF.md section 6, PR 38)."""
    if path[-1] in STD:
        return STD[path[-1]]
    return float(shape[-2]) ** -0.5          # fan-in of every matrix here


def forward_flops_per_token(config: dict) -> float:
    """Matmul FLOPs of one forward pass of one token at the cell's
    sequence length, from shapes, 2 a multiply-add.  Mamba-2: the in- and
    out-projection and the recurrence's 4 P N a head (write the outer
    product, read the state; the chunked form's extra products are not
    model FLOPs).  Attention: four projections, and Q K^T and P V over the
    causal half.  LatentMoE: router, the two latent projections, the
    shared expert, and the routed experts a token reaches here on average
    under even routing (held x top-k / routed), each two products in the
    latent width.  Head over the vocabulary's slice.  Norms, the short
    convolution, softplus, gates and softmax are not counted."""
    s = sizes(config)
    d, length = s["hidden"], config["sequence_length"]
    inner = s["mamba_heads"] * s["mamba_head_dim"]
    mamba = (2.0 * d * (2 * inner + 2 * s["mamba_groups"] * s["state_size"]
                        + s["mamba_heads"]) + 2.0 * inner * d
             + 4.0 * s["mamba_heads"] * s["mamba_head_dim"] * s["state_size"])
    wide, narrow = s["num_heads"] * s["head_dim"], \
        s["num_kv_heads"] * s["head_dim"]
    attention = (2.0 * d * (2 * wide + 2 * narrow)
                 + 4.0 * s["head_dim"] * s["num_heads"]
                 * (length * (length + 1) // 2) / length)
    reached = s["experts_held"] * s["top_k"] / s["num_experts"]
    moe = (2.0 * d * s["num_experts"] + 4.0 * d * s["latent"]
           + 4.0 * d * s["shared_width"]
           + reached * 4.0 * s["latent"] * s["expert_width"])
    total = 2.0 * d * s["vocab"]
    for mixer, ffn in s["layers"]:
        total += moe if ffn else (mamba if mixer == "mamba" else attention)
    return total


def train_flops_per_sample(config: dict) -> float:
    """A sample is one sequence.  Forward plus backward (twice the
    forward), no recomputation."""
    return 3.0 * config["sequence_length"] * forward_flops_per_token(config)


def ssd_scan_shape(config: dict) -> dict:
    """What the state-space recurrence sees in a step."""
    s = sizes(config)
    return {"tokens": config["per_chip_batch"] * config["sequence_length"],
            "heads": s["mamba_heads"], "head_dim": s["mamba_head_dim"],
            "groups": s["mamba_groups"], "state": s["state_size"],
            "layers": sum(m == "mamba" for m, _ in s["layers"])}


def ssd_scan_flops_per_step(shape: dict) -> float:
    """The recurrence's own 4 P N a token and head forward (write the
    outer product into the state, read it at C) and twice that backward:
    12 P N.  Whatever a chunked form multiplies besides is its own cost."""
    return (12.0 * shape["head_dim"] * shape["state"] * shape["tokens"]
            * shape["heads"] * shape["layers"])


def ssd_scan_bytes_per_step(shape: dict) -> float:
    """The least HBM traffic: forward reads X, B, C (2 B an element) and
    dt (4 B) and writes Y (2 B); backward reads those and dY again and
    writes dX, dB, dC (2 B) and ddt (4 B); the state never leaves the
    chip's fast memory."""
    x = shape["heads"] * shape["head_dim"]
    bc = 2 * shape["groups"] * shape["state"]
    forward = 2 * (x + bc) + 4 * shape["heads"] + 2 * x
    backward = forward + 2 * (x + bc) + 4 * shape["heads"]
    return float(forward + backward) * shape["tokens"] * shape["layers"]


def global_attention_shape(config: dict) -> dict:
    """What the attention layers' core sees in a step; `pairs`: L (L + 1)
    / 2 a sequence and head.  No reader takes it yet: the accepted
    `global_attn_roofline_pct` keys on another family's function (PERF.md,
    section 7)."""
    s = sizes(config)
    length = config["sequence_length"]
    return {"batch": config["per_chip_batch"], "heads": s["num_heads"],
            "kv_heads": s["num_kv_heads"], "length": length,
            "qk_dim": s["head_dim"], "v_dim": s["head_dim"],
            "pairs": length * (length + 1) // 2,
            "layers": sum(m == "attention" for m, _ in s["layers"])}


def global_attention_flops_per_step(shape: dict) -> float:
    """Forward Q K^T (2 e_qk) and P V (2 e_v) a seen pair and query head;
    backward dV, dP, dQ, dK: 6 (e_qk + e_v) in all.  The backward's
    recomputation of the scores and the rematerialised forward are the
    program's own cost and are not counted."""
    return (6.0 * (shape["qk_dim"] + shape["v_dim"]) * shape["pairs"]
            * shape["batch"] * shape["heads"] * shape["layers"])


def reference_loss(config: dict, nx):
    from benchmark.references import nemotron_h
    s = sizes(config)
    return lambda params, x, y: nemotron_h.loss(params, x, y, s, nx)
