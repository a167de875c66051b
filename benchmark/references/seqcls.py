"""Plain forward pass of the sequence classifier (pre-LayerNorm encoder,
learned positions, GELU MLP 4x, full softmax attention, mean pool, linear
head), written from its description.  Parameters are a nested dict under
the names the configuration's file lists; one layer at a time is
rematerialised so that BERT-large widths fit beside the weights."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.references.numerics import Numerics, cross_entropy


def layer_norm(x, p, eps=1e-6):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def encoder_layer(nx: Numerics, h, ln_a, attn, ln_m, mlp_in, mlp_out):
    a = layer_norm(h, ln_a)
    qkv = nx.einsum("bld,dthe->blthe", a, attn["qkv"]["kernel"])
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    s = nx.einsum("bqhe,bkhe->bhqk", q, k) / math.sqrt(q.shape[-1])
    p = jax.nn.softmax(s, axis=-1)
    o = nx.einsum("bhqk,bkhe->bqhe", p, v)
    o = o.reshape(o.shape[0], o.shape[1], -1)
    h = h + nx.einsum("blf,fd->bld", o, attn["proj"]["kernel"])
    m = layer_norm(h, ln_m)
    m = nx.einsum("bld,df->blf", m, mlp_in["kernel"]) + mlp_in["bias"]
    m = nx.einsum("blf,fd->bld", gelu_tanh(m), mlp_out["kernel"])
    return h + m + mlp_out["bias"]


def logits(params, tokens, num_layers: int, nx: Numerics):
    tokens = tokens.astype(jnp.int32)
    pos = jnp.arange(tokens.shape[1], dtype=jnp.int32)
    h = (params["tok_embed"]["embedding"][tokens]
         + params["pos_embed"]["embedding"][pos][None])
    layer = jax.checkpoint(lambda h_, *ps: encoder_layer(nx, h_, *ps))
    for i in range(num_layers):
        h = layer(h, params[f"ln_a{i}"], params[f"attn{i}"],
                  params[f"ln_m{i}"], params[f"mlp_in{i}"],
                  params[f"mlp_out{i}"])
    pooled = jnp.mean(layer_norm(h, params["ln_f"]), axis=1)
    return (nx.einsum("bd,dc->bc", pooled, params["head"]["kernel"])
            + params["head"]["bias"])


def loss(params, tokens, labels, num_layers: int, nx: Numerics):
    return cross_entropy(logits(params, tokens, num_layers, nx), labels)
