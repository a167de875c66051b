"""Plain forward pass of the CIFAR-form ResNet of BasicBlocks (He et al.
2016): 3x3 stem, stages of two 3x3 convolutions with batch statistics,
1x1 strided projection where the shape changes, mean pool, linear head.
Training mode: BatchNorm uses the batch's own mean and biased variance.
One block at a time is rematerialised so that a batch of 4,096 fits."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.references.numerics import Numerics, cross_entropy


def batch_norm(x, p, eps=1e-5):
    mu = jnp.mean(x, (0, 1, 2), keepdims=True)
    var = jnp.mean(jnp.square(x - mu), (0, 1, 2), keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def basic_block(nx: Numerics, x, p, stride: int):
    y = nx.conv(x, p["Conv_0"]["kernel"], stride)
    y = jax.nn.relu(batch_norm(y, p["BatchNorm_0"]))
    y = batch_norm(nx.conv(y, p["Conv_1"]["kernel"], 1), p["BatchNorm_1"])
    if "Conv_2" in p:
        x = batch_norm(nx.conv(x, p["Conv_2"]["kernel"], stride),
                       p["BatchNorm_2"])
    return jax.nn.relu(y + x)


def logits(params, images_u8, stage_sizes, nx: Numerics):
    x = images_u8.astype(jnp.float32) / 255.0
    x = nx.conv(x, params["Conv_0"]["kernel"], 1)
    x = jax.nn.relu(batch_norm(x, params["BatchNorm_0"]))
    b = 0
    for stage, blocks in enumerate(stage_sizes):
        for block in range(blocks):
            stride = 2 if stage > 0 and block == 0 else 1
            fn = jax.checkpoint(
                lambda x_, p_, s=stride: basic_block(nx, x_, p_, s))
            x = fn(x, params[f"BasicBlock_{b}"])
            b += 1
    x = jnp.mean(x, axis=(1, 2))
    return (nx.einsum("bd,dc->bc", x, params["Dense_0"]["kernel"])
            + params["Dense_0"]["bias"])


def loss(params, images_u8, labels, stage_sizes, nx: Numerics):
    return cross_entropy(logits(params, images_u8, stage_sizes, nx), labels)
