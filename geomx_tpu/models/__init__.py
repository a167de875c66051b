"""Model zoo for the geo-distributed training workloads.

The reference's demo workloads are Gluon CNNs on MNIST/FashionMNIST/CIFAR10
(examples/cnn*.py); the flagship target is ResNet on CIFAR10 (BASELINE.md).
"""

import jax.numpy as jnp

from geomx_tpu.models.afmoe import AfmoeConfig, AfmoeLM
from geomx_tpu.models.cnn import GeoCNN
from geomx_tpu.models.glm4_moe_lite import Glm4MoeLiteConfig, Glm4MoeLiteLM
from geomx_tpu.models.kimi_linear import KimiLinearConfig, KimiLinearLM
from geomx_tpu.models.mellum import MellumConfig, MellumLM
from geomx_tpu.models.mlp import MLP, AlexNet
from geomx_tpu.models.nemotron_h import NemotronHConfig, NemotronHLM
from geomx_tpu.models.ouro import OuroConfig, OuroLM
from geomx_tpu.models.resnet import (ResNet, ResNet18, ResNet20, ResNet32,
                                     ResNet56)
from geomx_tpu.models.seq_classifier import SeqClassifier

__all__ = ["GeoCNN", "MLP", "AlexNet",
           "ResNet", "ResNet20", "ResNet32", "ResNet56", "ResNet18",
           "SeqClassifier", "KimiLinearConfig", "KimiLinearLM", "AfmoeConfig",
           "AfmoeLM", "NemotronHConfig", "NemotronHLM", "MellumConfig",
           "MellumLM", "Glm4MoeLiteConfig", "Glm4MoeLiteLM", "OuroConfig",
           "OuroLM", "get_model"]

# GEOMX_PRECISION -> the models' compute dtype.  Params always stay
# fp32 (flax casts per-op from the fp32 masters); every model's
# classifier head computes and returns fp32 regardless (train/step.py).
_PRECISION_DTYPE = {"fp32": jnp.float32, "bf16": jnp.bfloat16}


def get_model(name: str, num_classes: int = 10, precision: str = None,
              **sizes):
    """Build a zoo model.  ``precision`` (``"fp32"``/``"bf16"``, as
    resolved by ``train.step.resolve_precision``) pins the compute
    dtype explicitly; the default ``None`` keeps each model's
    historical default (byte-identical traces).  ``sizes``: the fields of
    `KimiLinearConfig` for ``"kimi_linear"``, of `AfmoeConfig` for
    ``"afmoe"``, of `NemotronHConfig` for ``"nemotron_h"``, of
    `MellumConfig` for ``"mellum"``, of `Glm4MoeLiteConfig` for
    ``"glm4_moe_lite"`` and of `OuroConfig` for ``"ouro"``, causal decoders
    that bring their own next-token loss (no ``num_classes``)."""
    name = name.lower()
    dt = {}
    if precision is not None:
        dt = {"dtype": _PRECISION_DTYPE[precision]}
    if name == "kimi_linear":
        return KimiLinearLM(KimiLinearConfig(**sizes), **dt)
    if name == "afmoe":
        return AfmoeLM(AfmoeConfig(**sizes), **dt)
    if name == "nemotron_h":
        return NemotronHLM(NemotronHConfig(**sizes), **dt)
    if name == "mellum":
        return MellumLM(MellumConfig(**sizes), **dt)
    if name == "glm4_moe_lite":
        return Glm4MoeLiteLM(Glm4MoeLiteConfig(**sizes), **dt)
    if name == "ouro":
        return OuroLM(OuroConfig(**sizes), **dt)
    if name in ("cnn", "geocnn", "lenet"):
        return GeoCNN(num_classes=num_classes, **dt)
    if name == "mlp":
        return MLP(num_classes=num_classes, **dt)
    if name == "alexnet":
        return AlexNet(num_classes=num_classes, **dt)
    if name == "resnet20":
        return ResNet20(num_classes=num_classes, **dt)
    if name in ("resnet20_s2d", "resnet20-s2d"):
        # TPU-optimized variant: 2x2 space-to-depth stem + MXU-friendly
        # transition shortcuts (see models/resnet.py)
        return ResNet20(num_classes=num_classes, space_to_depth=True,
                        mxu_shortcuts=True, **dt)
    if name == "resnet32":
        return ResNet32(num_classes=num_classes, **dt)
    if name == "resnet56":
        return ResNet56(num_classes=num_classes, **dt)
    if name == "resnet18":
        return ResNet18(num_classes=num_classes, **dt)
    if name in ("seq", "seq_classifier", "transformer"):
        return SeqClassifier(num_classes=num_classes, **dt)
    raise ValueError(f"Unknown model: {name!r}")
