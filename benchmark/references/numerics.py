"""The one place where a reference chooses its matmul precision.

`Numerics("float32")` is the reference proper: float32 operands, every
contraction at `Precision.HIGHEST` (on a TPU a float32 matmul otherwise
rounds its operands to bfloat16).  The lower rungs exist for the control
of "How correct is decided": the same reference computed in the nearest
precision below the one the configuration states, which the comparison has
to refuse.  `bfloat16` rounds each contraction's operands to bfloat16;
`float8` rounds them to float8_e4m3fn under a per-tensor scale (amax/448,
the usual dynamic scaling), with a straight-through derivative so that the
backward pass sees the rounded operands and unrounded cotangents.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LADDER = ("float64", "float32", "bfloat16", "float8", "int4")


def next_lower(stated: str) -> str:
    """The control's precision for a configuration that states `stated`."""
    return LADDER[LADDER.index(stated) + 1]


@jax.custom_jvp
def _round_float8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


@_round_float8.defjvp
def _round_float8_jvp(primals, tangents):
    return _round_float8(primals[0]), tangents[0]


@jax.custom_jvp
def _round_bfloat16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


@_round_bfloat16.defjvp
def _round_bfloat16_jvp(primals, tangents):
    return _round_bfloat16(primals[0]), tangents[0]


class Numerics:
    def __init__(self, precision: str = "float32"):
        if precision not in ("float32", "bfloat16", "float8"):
            raise ValueError(f"no reference arithmetic for {precision!r}")
        self.precision = precision

    def operand(self, x):
        x = x.astype(jnp.float32)
        if self.precision == "bfloat16":
            return _round_bfloat16(x)
        if self.precision == "float8":
            return _round_float8(x)
        return x

    def einsum(self, spec, a, b):
        return jnp.einsum(spec, self.operand(a), self.operand(b),
                          precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)

    def conv(self, x, kernel, stride: int):
        return jax.lax.conv_general_dilated(
            self.operand(x), self.operand(kernel), (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)


def cross_entropy(logits, labels):
    """Mean softmax cross-entropy with integer labels."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None].astype(jnp.int32),
                                 axis=-1)[:, 0]
    return jnp.mean(logz - picked)
