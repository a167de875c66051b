"""Family `resnet`: the repo's `models.ResNet` (CIFAR form: 3x3 stem,
stages of BasicBlocks, BatchNorm, mean pool, linear head)."""
from __future__ import annotations

import numpy as np


def build_model(config: dict):
    import jax.numpy as jnp
    from geomx_tpu.models import ResNet
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        config["precision"]]
    return ResNet(stage_sizes=tuple(config["stage_sizes"]),
                  stage_filters=tuple(config["stage_filters"]),
                  num_classes=config["num_classes"],
                  stem_kernel=config["stem_kernel"], dtype=dtype)


def make_data(config: dict, rng: np.random.Generator, rows: int):
    size = config["image_size"]
    x = rng.integers(0, 256, (rows, size, size, 3), dtype=np.uint8)
    y = rng.integers(0, config["num_classes"], (rows,), dtype=np.int32)
    return x, y


def weight_std(path, shape) -> float:
    if path[-1] == "kernel":                 # He: sqrt(2 / fan-in)
        return (2.0 / float(np.prod(shape[:-1]))) ** 0.5
    return 0.02                              # biases


def conv_shapes(config: dict):
    """(kernel, c_in, c_out, output side) of every convolution."""
    side, c_in = config["image_size"], 3
    k = config["stem_kernel"]
    out = [(k, c_in, config["stage_filters"][0], side)]
    c_in = config["stage_filters"][0]
    for stage, (blocks, c_out) in enumerate(
            zip(config["stage_sizes"], config["stage_filters"])):
        for block in range(blocks):
            stride = 2 if stage > 0 and block == 0 else 1
            side //= stride
            out.append((3, c_in, c_out, side))
            out.append((3, c_out, c_out, side))
            if stride != 1 or c_in != c_out:
                out.append((1, c_in, c_out, side))
            c_in = c_out
    return out


def forward_flops_per_sample(config: dict) -> float:
    """Convolution and head FLOPs of one forward pass of one image, from
    shapes, 2 per multiply-add.  BatchNorm, ReLU and pooling are not
    counted."""
    flops = sum(2.0 * k * k * ci * co * side * side
                for k, ci, co, side in conv_shapes(config))
    return flops + 2.0 * config["stage_filters"][-1] * config["num_classes"]


def train_flops_per_sample(config: dict) -> float:
    return 3.0 * forward_flops_per_sample(config)


def reference_loss(config: dict, nx):
    from benchmark.references import resnet
    stages = tuple(config["stage_sizes"])
    return lambda params, x, y: resnet.loss(params, x, y, stages, nx)
