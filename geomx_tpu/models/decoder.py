"""What the causal decoders share (`models/kimi_linear.py`,
`models/afmoe.py`, `models/nemotron_h.py`, `models/mellum.py`): RMSNorm,
the SwiGLU MLP, the router, the expert layer that holds some of its experts
(`ops/held_experts.py`), the residual block whose two halves are
rematerialised apart, the model around the blocks and its blocked
next-token loss.

    h += [PostNorm](Mixer(RMSNorm(h)));  h += [PostNorm](FFN(RMSNorm(h)))

A block has both halves or ONE of them ((mixer, None) or (None, ffn): a
Nemotron-H layer is a mixer alone or a feed-forward alone, one norm and
one residual add).

A decoder's configuration (a frozen dataclass) gives the widths the
feed-forward half reads (`FFNBranch`), `layers` ((mixer, ffn) a block,
either None), `vocab`, `hidden`, `eps`, `loss_block`, `remat`, and four
things of its own: `make_mixer(kind, dtype)` (the module under ``core``
of a block's mixer half), `post_norms` (a second RMSNorm on each half's
output), `embedding_scale` and `expert_form` (further fields of
`HeldExpertsLayer`: {} for SwiGLU experts in the hidden width; a
LatentMoE gives ``latent``, ``gated`` False and ``shared_width``; a
softmax router gives ``scoring``).

The model brings its own loss (`loss_and_aux`): mean next-token
cross-entropy in float32, blocked over tokens so that no whole logits
array lives; `train/step.make_loss_fn` takes it from there.  In the
backward pass each block's mixer is rematerialised a sequence at a time and
its feed-forward half on its own.

Scopes (telemetry/layers.SCOPES): ``moe/route``, ``moe/experts`` (inside
it ``ops/held_experts``' own ``moe/plan`` and ``moe/dispatch``),
``moe/shared``, ``moe/latent``, ``lm/loss``, and the mixers' own.  Module
names are ``mixer``, ``ffn``, ``core``, ``norm`` and ``post_norm`` so that
flax's own name stack never reads as one of them.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from geomx_tpu.ops.held_experts import held_experts, places_walked
from geomx_tpu.utils.profiler import profile_scope

_HIGHEST = lax.Precision.HIGHEST


class RMSNorm(nn.Module):
    eps: float

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        y = x32 * lax.rsqrt(
            jnp.mean(jnp.square(x32), -1, keepdims=True) + self.eps)
        return (y * scale).astype(x.dtype)


def _normal(std: float = 0.02):
    return nn.initializers.normal(std)


def _fan_in(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype) * shape[-2] ** -0.5


def swiglu(x, gate, up, down):
    return jnp.dot(jax.nn.silu(jnp.dot(x, gate)) * jnp.dot(x, up), down)


def relu2(x, up, down):
    """The un-gated squared-ReLU MLP, relu(x W1)^2 W2."""
    return jnp.dot(jnp.square(jax.nn.relu(jnp.dot(x, up))), down)


def causal_conv(x, kernel):
    """Depthwise causal convolution over time, heads-major: x [B, H, L,
    e], kernel [taps, H, e]; tap ``taps - 1`` multiplies the current
    token."""
    taps, length = kernel.shape[0], x.shape[2]
    padded = jnp.pad(x, ((0, 0), (0, 0), (taps - 1, 0), (0, 0)))
    return sum(padded[:, :, j:j + length] * kernel[j][:, None, :]
               for j in range(taps))


class HeadScale(nn.Module):
    """The learned scale of a norm applied by its caller (a per-head or
    per-group RMSNorm), where `RMSNorm` keeps it (``<name>/scale``,
    ones)."""

    @nn.compact
    def __call__(self, d: int):
        return self.param("scale", nn.initializers.ones, (d,))


class MLP(nn.Module):
    width: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        hidden, dt = x.shape[-1], self.dtype
        mat = lambda name, shape: self.param(name, _fan_in, shape).astype(dt)
        return swiglu(x, mat("gate_kernel", (hidden, self.width)),
                      mat("up_kernel", (hidden, self.width)),
                      mat("down_kernel", (self.width, hidden)))


def route(x, router, bias, top_k: int, scaling: float,
          scoring: str = "sigmoid"):
    """Scores in float32 (``scoring`` "sigmoid": each expert's own;
    "softmax": probabilities over all the experts), the ``top_k`` largest
    of score + bias, weights normalised over the selected and scaled.
    x [T, d].  The
    picked scores are read by a compare-and-sum over the experts, not by
    a gather: XLA's gather of [T, k] out of [T, E] costs 10 ns an index on
    a v5e, its transpose (a scatter-add) 9 ns, and both want the scores
    relaid flat (52 of a 572 ms step at 22 of 512: PERF.md, PR 38); the
    sum's one non-zero term is the score itself, forward and backward, so
    nothing is rounded."""
    squash = {"sigmoid": jax.nn.sigmoid, "softmax": jax.nn.softmax}[scoring]
    scores = squash(jnp.dot(x.astype(jnp.float32), router,
                            precision=_HIGHEST))
    _, idx = lax.top_k(scores + bias, top_k)
    hit = idx[..., None] == jnp.arange(scores.shape[-1])     # [T, k, E]
    picked = jnp.sum(jnp.where(hit, scores[:, None, :], 0.0), -1)
    return idx, scaling * picked / jnp.sum(picked, -1, keepdims=True)


class HeldExpertsLayer(nn.Module):
    """Routes over ``num_experts`` (`route` under ``scoring``), holds
    ``num_held`` of them from ``offset`` on, and computes the shared expert
    plus its own experts' part of the result; with ``shared_experts`` 0
    (and no ``shared_width``) there is no shared expert: no shared kernels,
    no ``moe/shared`` scope, the result is the held experts' part alone.
    The selection bias is not trained by gradient:
    zeros, outside ``params``.  ``gated`` False: shared and routed experts
    are un-gated squared-ReLU MLPs (no gate kernels).  ``latent``: the
    routed experts live in that width, between a down- and an up-
    projection of their own (LatentMoE; router and shared expert read the
    hidden state).  ``shared_width``: the shared expert's own width where
    it is not ``shared_experts x width``.  Returns (y, assignments that
    arrived at each held expert [num_held], assignments dropped: 0)."""
    num_experts: int
    num_held: int
    offset: int
    top_k: int
    width: int
    scaling: float
    shared_experts: int = 1
    rows: int = 512             # assignments a tile of the kernels holds
    dtype: Any = jnp.float32
    pool: int | None = None     # places of the first pool; None: 2 E rows
    gated: bool = True
    latent: int | None = None
    shared_width: int | None = None
    scoring: str = "sigmoid"

    @nn.compact
    def __call__(self, x):
        hidden, dt = x.shape[-1], self.dtype
        tokens = x.reshape(-1, hidden)
        mat = lambda name, shape: self.param(name, _fan_in, shape)
        cast = lambda name, shape: mat(name, shape).astype(dt)
        with profile_scope("moe/route", "compute"):
            idx, weights = route(
                tokens, mat("router_kernel", (hidden, self.num_experts)),
                jnp.zeros((self.num_experts,), jnp.float32), self.top_k,
                self.scaling, self.scoring)
        wide = self.shared_width or self.shared_experts * self.width
        y = None
        if wide:
            with profile_scope("moe/shared", "compute"):
                gate = ([cast("shared_gate_kernel", (hidden, wide))]
                        if self.gated else [])
                y = (swiglu if self.gated else relu2)(
                    tokens, *gate, cast("shared_up_kernel", (hidden, wide)),
                    cast("shared_down_kernel", (wide, hidden)))
        inner = self.latent or hidden
        if self.latent:
            with profile_scope("moe/latent", "compute"):
                tokens = jnp.dot(tokens,
                                 cast("latent_down_kernel", (hidden, inner)))
        with profile_scope("moe/experts", "compute"):
            into = (self.num_held, inner, self.width)
            routed, counts, dropped = held_experts(
                tokens, idx, weights,
                mat("experts_gate_kernel", into) if self.gated else None,
                mat("experts_up_kernel", into),
                mat("experts_down_kernel",
                    (self.num_held, self.width, inner)),
                self.offset, self.rows, None, self.pool)
            if not self.latent:
                y = (routed if y is None else y + routed).astype(dt)
        if self.latent:
            with profile_scope("moe/latent", "compute"):
                up = jnp.dot(routed.astype(dt),
                             cast("latent_up_kernel", (inner, hidden)))
                y = up if y is None else y + up
        return y.reshape(x.shape), counts, dropped


class MixerBranch(nn.Module):
    """``h + [PostNorm](Mixer(RMSNorm(h)))`` for ONE sequence ``h``
    [L, hidden], in `nn.scan`'s (carry, x) form: a block runs it sequence
    by sequence, each rematerialised on its own, so that the backward pass
    holds one sequence's mixer internals at a time (at 8,192 tokens a KDA
    layer's are ~2 GB)."""
    kind: str                   # the configuration's own mixer kinds
    cfg: Any
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, carry, h):
        c = self.cfg
        x = RMSNorm(c.eps, name="norm")(h[None])
        y = c.make_mixer(self.kind, self.dtype)(x)
        if c.post_norms:
            y = RMSNorm(c.eps, name="post_norm")(y)
        return carry, h + y[0]


class FFNBranch(nn.Module):
    """``h + [PostNorm](FFN(RMSNorm(h)))`` over the whole batch; returns
    (h, assignments that arrived at each held expert, assignments
    dropped)."""
    kind: str                   # "mlp" | "moe"
    cfg: Any
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h):
        c, dt = self.cfg, self.dtype
        x = RMSNorm(c.eps, name="norm")(h)
        if self.kind == "mlp":
            y = MLP(c.dense_width, dt, name="core")(x)
            counts, dropped = jnp.zeros((0,), jnp.int32), \
                jnp.zeros((), jnp.int32)
        else:
            y, counts, dropped = HeldExpertsLayer(
                c.num_experts, c.experts_held, c.expert_offset, c.top_k,
                c.expert_width, c.routed_scaling, c.shared_experts,
                c.expert_rows, dt, c.expert_pool, name="core",
                **c.expert_form)(x)
        if c.post_norms:
            y = RMSNorm(c.eps, name="post_norm")(y)
        return h + y, counts, dropped


class Block(nn.Module):
    """The halves it has, mixer first: ``mixer`` or ``ffn`` None leaves
    that half out, parameters and all.  Returns (h, assignments that
    arrived at each held expert: none without an expert layer, assignments
    dropped)."""
    mixer: Optional[str]
    ffn: Optional[str]
    cfg: Any
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h):
        remat = nn.remat if self.cfg.remat else (lambda m, **_: m)
        if self.mixer is not None:
            per_sequence = nn.scan(
                remat(MixerBranch, prevent_cse=False),
                variable_broadcast="params", split_rngs={"params": False})
            _, h = per_sequence(self.mixer, self.cfg, self.dtype,
                                name="mixer")((), h)
        if self.ffn is None:
            return h, jnp.zeros((0,), jnp.int32), jnp.zeros((), jnp.int32)
        return remat(FFNBranch)(self.ffn, self.cfg, self.dtype,
                                name="ffn")(h)


class DecoderLM(nn.Module):
    cfg: Any
    dtype: Any = jnp.float32

    def setup(self):
        c = self.cfg
        self.embedding = self.param("embedding", _normal(0.02),
                                    (c.vocab, c.hidden))
        self.blocks = [Block(mixer, ffn, c, self.dtype, name=f"layer{i + 1}")
                       for i, (mixer, ffn) in enumerate(c.layers)]
        self.final_norm = RMSNorm(c.eps, name="final_norm")
        self.head_kernel = self.param("head_kernel", _fan_in,
                                      (c.hidden, c.vocab))

    def features(self, tokens):
        """(normed features [B, L, hidden], assignments that arrived at
        each held expert of each expert layer, assignments dropped)."""
        h = self.embedding.astype(self.dtype)[tokens.astype(jnp.int32)]
        if self.cfg.embedding_scale != 1.0:
            h = h * jnp.asarray(self.cfg.embedding_scale, self.dtype)
        arrived, dropped = [], jnp.zeros((), jnp.int32)
        for block in self.blocks:
            h, counts, lost = block(h)
            arrived.append(counts)
            dropped = dropped + lost
        return self.final_norm(h), jnp.concatenate(arrived), dropped

    def __call__(self, tokens, train: bool = False):
        """Whole logits [B, L, vocab] in float32: init, eval, small
        inputs.  Training takes `loss_and_aux`."""
        return jnp.dot(self.features(tokens)[0],
                       self.head_kernel.astype(self.dtype),
                       preferred_element_type=jnp.float32)

    def loss_and_aux(self, tokens, labels, train: bool = True):
        """(mean cross-entropy of ``labels`` [B, L], aux).  ``aux`` holds
        ``accuracy`` and, where an expert layer exists, ``counters``:
        scalars a step (assignments per held expert and layer as min,
        mean, max, those dropped, and the rows the expert layers' dispatch
        moved over the places their pools walked)."""
        h, arrived, dropped = self.features(tokens)
        with profile_scope("lm/loss", "compute"):
            total, hits = blocked_cross_entropy(
                h.reshape(-1, h.shape[-1]),
                self.head_kernel.astype(self.dtype), labels.reshape(-1),
                self.cfg.loss_block)
        aux = {"accuracy": hits / labels.size}
        if arrived.size:
            c = self.cfg
            walked = jnp.sum(places_walked(
                jnp.sum(arrived.reshape(-1, c.experts_held), axis=1),
                c.experts_held, c.expert_rows, c.expert_pool))
            moved = jnp.sum(arrived) - dropped
            arrived = arrived.astype(jnp.float32)
            aux["counters"] = {
                "moe/assignments_min": jnp.min(arrived),
                "moe/assignments_mean": jnp.mean(arrived),
                "moe/assignments_max": jnp.max(arrived),
                "moe/dropped": dropped.astype(jnp.float32),
                "moe/pool_fill": moved / walked.astype(jnp.float32)}
        return total / labels.size, aux


def blocked_cross_entropy(h, head, labels, block: int):
    """(sum of cross-entropies, number of argmax hits) over tokens h
    [T, d], ``block`` tokens at a time: a block's float32 logits are the
    most that lives, forward and (rematerialised) backward."""
    t = h.shape[0]
    block = min(block, t)
    pad = (-t) % block
    if pad:
        h = jnp.pad(h, ((0, pad), (0, 0)))
        labels = jnp.pad(labels, (0, pad), constant_values=-1)

    @jax.checkpoint
    def one(carry, xs):
        h_, y_ = xs
        logits = jnp.dot(h_, head, preferred_element_type=jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(
            logits, jnp.maximum(y_, 0)[:, None], axis=-1)[:, 0]
        real = y_ >= 0
        hits = jnp.sum(real & (jnp.argmax(logits, -1) == y_))
        return (carry[0] + jnp.sum(jnp.where(real, logz - picked, 0.0)),
                carry[1] + hits.astype(jnp.float32)), None

    (total, hits), _ = lax.scan(
        one, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (h.reshape(-1, block, h.shape[-1]),
         labels.astype(jnp.int32).reshape(-1, block)))
    return total, hits
