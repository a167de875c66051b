"""Family `seqcls`: the repo's SeqClassifier (pre-LayerNorm encoder,
learned positions, GELU MLP 4x, flash attention through fused_attention,
mean pool, linear head) under a configuration's widths."""
from __future__ import annotations

import numpy as np


def build_model(config: dict):
    import jax.numpy as jnp
    from geomx_tpu.models import SeqClassifier
    if config["intermediate_size"] != 4 * config["hidden_size"]:
        raise ValueError("SeqClassifier's MLP is 4x the hidden size")
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        config["precision"]]
    return SeqClassifier(
        vocab=config["vocab_size"],
        max_len=config["max_position_embeddings"],
        dim=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_layers=config["num_hidden_layers"],
        num_classes=config["num_classes"], dtype=dtype)


def make_data(config: dict, rng: np.random.Generator, rows: int):
    """Seeded tokens and labels; every row differs."""
    x = rng.integers(0, config["vocab_size"],
                     (rows, config["sequence_length"]), dtype=np.int32)
    y = rng.integers(0, config["num_classes"], (rows,), dtype=np.int32)
    return x, y


def weight_std(path, shape) -> float:
    if path[-1] == "embedding":
        return 0.02
    if path[-1] == "kernel":
        return float(shape[0]) ** -0.5      # fan-in of every Dense here
    return 0.02                              # biases


def forward_flops_per_sample(config: dict) -> float:
    """Matmul FLOPs of one forward pass of one sequence, from shapes:
    per token and layer 24 d^2 (qkv 6, proj 2, MLP 16) and 4 L d for the
    two attention products; the head; 2 FLOPs per multiply-add.
    Embedding lookups, LayerNorm, softmax and GELU are not counted."""
    d, length = config["hidden_size"], config["sequence_length"]
    per_token_layer = 24.0 * d * d + 4.0 * length * d
    return (length * config["num_hidden_layers"] * per_token_layer
            + 2.0 * d * config["num_classes"])


def train_flops_per_sample(config: dict) -> float:
    """Forward plus backward (twice the forward), no recomputation."""
    return 3.0 * forward_flops_per_sample(config)


def attention_shape(config: dict) -> dict:
    """What one call of the attention kernel sees, per layer."""
    return {"batch": config["per_chip_batch"],
            "heads": config["num_attention_heads"],
            "length": config["sequence_length"],
            "head_dim": config["hidden_size"] // config["num_attention_heads"],
            "layers": config["num_hidden_layers"]}


def reference_loss(config: dict, nx):
    from benchmark.references import seqcls
    layers = config["num_hidden_layers"]
    return lambda params, x, y: seqcls.loss(params, x, y, layers, nx)
