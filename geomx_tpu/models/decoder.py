"""What the causal decoders share (`models/kimi_linear.py`,
`models/afmoe.py`, `models/nemotron_h.py`, `models/mellum.py`,
`models/glm4_moe_lite.py`): RMSNorm, the SwiGLU MLP, the latent-attention
mixer, the router, the expert layer that holds some of its experts
(`ops/held_experts.py`), the residual block whose two halves are
rematerialised apart, the model around the blocks, its blocked next-token
loss and the multi-token-prediction modules behind it.

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
softmax router gives ``scoring``).  A configuration may also give
``mtp_depth`` (0 where it does not), with ``mtp_weight`` and ``mtp_block``
((mixer, ffn) of a module's block): see `MTPModule`; and ``loops`` (1 where
it does not) with ``exit_beta``: the whole stack applied ``loops`` times
over the same parameters, a head pass and an exit gate a
loop step (`DecoderLM.features`, `DecoderLM.looped_loss`).

The model brings its own loss (`loss_and_aux`): mean next-token
cross-entropy in float32, blocked over tokens so that no whole logits
array lives, its gradient made in the same pass
(`blocked_cross_entropy`); `train/step.make_loss_fn` takes it from
there.  In the backward pass each block's mixer is rematerialised a
sequence at a time and its feed-forward half on its own.

Scopes (telemetry/layers.SCOPES): ``mla/proj`` and ``mla/attention``
(`LatentMixer`), ``moe/route``, ``moe/experts`` (inside it
``ops/held_experts``' own ``moe/plan`` and ``moe/dispatch``),
``moe/shared``, ``moe/latent``, ``ffn/mlp`` (the dense SwiGLU half),
``block/norm`` (the residual stream's norms and adds: a half's ``norm``,
``post_norm`` and ``h + y``, the final norm, a prediction module's output
norm), ``lm/embed`` (the lookup, its multiplier and cast),
``lm/loss``, ``loop/exit`` (a looped stack's exit gate, distribution and
entropy), ``mtp/module`` and inside it ``mtp/combine``, and the other
mixers' own.  Module names are ``mixer``,
``ffn``, ``core``, ``norm``, ``post_norm``, ``block``, ``exit_gate`` and
``mtp<k>`` so
that flax's own name stack never reads as one of them.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from geomx_tpu.ops.flash_attention import fused_attention
from geomx_tpu.ops.gqa_elementwise import rotary_tables
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


class LatentMixer(nn.Module):
    """Latent attention (MLA): keys and values through a ``kv_rank``-wide
    latent with its own RMSNorm, each head's key a ``nope_dim`` part of its
    own beside ONE ``rope_dim`` part all heads share, ``v_dim``-wide values,
    causal softmax(q k^T / sqrt(nope + rope)) v through
    `ops/flash_attention.fused_attention`.

    ``q_rank`` None: one W_q (``q_kernel``); else the query goes through a
    latent of that width with its own RMSNorm (``q_a_kernel``, ``q_norm``,
    ``q_b_kernel``).  ``rope`` None: no positions at all; else rotate-half
    rotary over the ``rope_dim`` parts from the tables
    `ops/gqa_elementwise.rotary_tables` makes of it (a theta or a `Yarn`),
    positions 0..L-1: each head's of q, and the shared key part once,
    before it is handed to every head."""
    num_heads: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    kv_rank: int
    eps: float
    dtype: Any = jnp.float32
    q_rank: Optional[int] = None
    rope: Any = None

    @nn.compact
    def __call__(self, x):
        h, dt, hidden = self.num_heads, self.dtype, x.shape[-1]
        b, length, _ = x.shape
        qk = self.nope_dim + self.rope_dim
        mat = lambda name, shape: self.param(name, _fan_in, shape)
        with profile_scope("mla/proj", "compute"):
            if self.q_rank is None:
                q = jnp.dot(x, mat("q_kernel", (hidden, h * qk)).astype(dt))
            else:
                q = RMSNorm(self.eps, name="q_norm")(jnp.dot(
                    x, mat("q_a_kernel", (hidden, self.q_rank)).astype(dt)))
                q = jnp.dot(q, mat("q_b_kernel",
                                   (self.q_rank, h * qk)).astype(dt))
            kv = jnp.dot(x, mat("kv_a_kernel",
                                (hidden, self.kv_rank + self.rope_dim))
                         .astype(dt))
            latent = RMSNorm(self.eps, name="kv_norm")(
                kv[..., :self.kv_rank])
            shared = kv[..., self.kv_rank:]        # one key part, all heads
            kv_b = jnp.dot(latent, mat(
                "kv_b_kernel", (self.kv_rank, h * (self.nope_dim + self.v_dim))
            ).astype(dt)).reshape(b, length, h, self.nope_dim + self.v_dim)
            if self.rope is not None:
                cos, sin = rotary_tables(length, self.rope_dim, self.rope)
                shared = turn(shared, cos, sin)
            k = jnp.concatenate(
                [kv_b[..., :self.nope_dim], jnp.broadcast_to(
                    shared[:, :, None, :], (b, length, h, self.rope_dim))],
                -1)
            v = kv_b[..., self.nope_dim:]
            q = q.reshape(b, length, h, qk)
            if self.rope is not None:
                q = jnp.concatenate(
                    [q[..., :self.nope_dim],
                     turn(q[..., self.nope_dim:], cos[:, None], sin[:, None])],
                    -1)
        with profile_scope("mla/attention", "kernel"):
            o = fused_attention(q, k, v, True)
        with profile_scope("mla/proj", "compute"):
            return jnp.dot(o.reshape(b, length, h * self.v_dim),
                           mat("out_kernel", (h * self.v_dim, hidden))
                           .astype(dt))


def turn(x, cos, sin):
    """Rotate-half rotary of the last axis under `rotary_tables`' (cos,
    signed sin), broadcastable to x; arithmetic in float32."""
    x32 = x.astype(jnp.float32)
    return (x32 * cos + jnp.roll(x32, x.shape[-1] // 2, -1) * sin).astype(
        x.dtype)


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
        with profile_scope("block/norm", "compute"):
            x = RMSNorm(c.eps, name="norm")(h[None])
        y = c.make_mixer(self.kind, self.dtype)(x)
        with profile_scope("block/norm", "compute"):
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
        with profile_scope("block/norm", "compute"):
            x = RMSNorm(c.eps, name="norm")(h)
        if self.kind == "mlp":
            with profile_scope("ffn/mlp", "compute"):
                y = MLP(c.dense_width, dt, name="core")(x)
            counts, dropped = jnp.zeros((0,), jnp.int32), \
                jnp.zeros((), jnp.int32)
        else:
            y, counts, dropped = HeldExpertsLayer(
                c.num_experts, c.experts_held, c.expert_offset, c.top_k,
                c.expert_width, c.routed_scaling, c.shared_experts,
                c.expert_rows, dt, c.expert_pool, name="core",
                **c.expert_form)(x)
        with profile_scope("block/norm", "compute"):
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


class MTPModule(nn.Module):
    """One depth of multi-token prediction (DeepSeek-V3, arXiv:2412.19437,
    eq. 21-23): the stream of the depth before (the main model's before
    its final norm at depth 1) and the embedding of the token one further
    on, each under an RMSNorm of its own, joined by one matrix, through
    one more block:

        h' = [N_h(h) ; N_e(Emb(t))] W_eh;   h'' = Block(h')

    Returns (h'' for the next depth, N_out(h'') for the shared head, the
    block's assignments arrived and dropped).  Embedding and head are the
    model's own and stay with it."""
    cfg: Any
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, stream, embedded):
        c, dt, hidden = self.cfg, self.dtype, stream.shape[-1]
        with profile_scope("mtp/combine", "compute"):
            joined = jnp.concatenate(
                [RMSNorm(c.eps, name="hidden_norm")(stream),
                 RMSNorm(c.eps, name="token_norm")(embedded)], -1)
            h = jnp.dot(joined, self.param(
                "join_kernel", _fan_in, (2 * hidden, hidden)).astype(dt))
        h, counts, dropped = Block(*c.mtp_block, c, dt, name="block")(h)
        with profile_scope("block/norm", "compute"):
            normed = RMSNorm(c.eps, name="out_norm")(h)
        return h, normed, counts, dropped


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
        self.mtp = [MTPModule(c, self.dtype, name=f"mtp{k + 1}")
                    for k in range(getattr(c, "mtp_depth", 0))]
        self.loops = getattr(c, "loops", 1)
        if self.loops > 1:
            if self.mtp:
                raise ValueError("no multi-token prediction behind a looped "
                                 "stack: which pass's stream would it read")
            self.exit_gate = ExitGate(name="exit_gate")

    def embed(self, tokens):
        with profile_scope("lm/embed", "compute"):
            h = self.embedding.astype(self.dtype)[tokens.astype(jnp.int32)]
            if self.cfg.embedding_scale != 1.0:
                h = h * jnp.asarray(self.cfg.embedding_scale, self.dtype)
            return h

    def stack(self, h):
        """One pass of the blocks and the final norm: (normed stream,
        assignments that arrived at each held expert of each expert layer,
        assignments dropped, the stream before the final norm)."""
        arrived, dropped = [], jnp.zeros((), jnp.int32)
        for block in self.blocks:
            h, counts, lost = block(h)
            arrived.append(counts)
            dropped = dropped + lost
        with profile_scope("block/norm", "compute"):
            normed = self.final_norm(h)
        return normed, jnp.concatenate(arrived), dropped, h

    def features(self, tokens):
        """(normed features [B, L, hidden], assignments that arrived at
        each held expert of each expert layer, assignments dropped, the
        stream before the final norm).  With ``loops`` T > 1 the stack runs
        T times over the same parameters, the NORMED stream of a pass the
        input of the next, and the features are the T normed streams
        [T, B, L, hidden] (the counts those of the T passes, the last
        pass's stream before its norm).  The T passes stand in the program
        one after the other: as one scan over the loop steps the step
        compiles in 38 s and not 64 and holds 1.35 GiB less, but it is a
        fifth slower (0.865 against 1.033 samples/s/chip at 4 x 6 blocks
        of 8,192 tokens: inside a `while` every mixer half really is
        rematerialised, where XLA here shares a pass's attention with its
        rematerialised copy; PERF.md section 6, PR 48)."""
        h = self.embed(tokens)
        if self.loops == 1:
            return self.stack(h)
        streams, arrived, dropped = [], [], jnp.zeros((), jnp.int32)
        for _ in range(self.loops):
            h, counts, lost, before = self.stack(h)
            streams.append(h)
            arrived.append(counts)
            dropped = dropped + lost
        return jnp.stack(streams), jnp.concatenate(arrived), dropped, before

    def __call__(self, tokens, train: bool = False):
        """Whole logits [B, L, vocab] in float32 (a looped stack's: the
        last pass's): init, eval, small inputs.  Training takes
        `loss_and_aux`."""
        h, _, _, stream = self.features(tokens)
        if self.is_initializing():      # the modules' parameters exist too
            for module in self.mtp:
                stream = module(stream, self.embed(tokens))[0]
        if self.loops > 1:
            if self.is_initializing():
                self.exit_gate(h)
            h = h[-1]
        return jnp.dot(h, self.head_kernel.astype(self.dtype),
                       preferred_element_type=jnp.float32)

    def mtp_losses(self, stream, labels):
        """The modules' chain over ``stream`` [B, L, hidden] (the main
        model's before its final norm): depth k joins position i's stream
        of depth k - 1 with the embedding of token i + k (``labels``
        shifted k - 1 to the left) and is held to token i + k + 1
        (``labels`` shifted k) at the L - k positions a row that have one;
        the module runs on all L positions, the others' labels masked:
        causal, so nothing that counts reads them.  Returns (mean
        cross-entropy of each depth [D], argmax hits of each depth [D],
        assignments arrived, dropped)."""
        b, length = labels.shape
        ahead = lambda k, fill: jnp.pad(
            labels[:, k:], ((0, 0), (0, k)), constant_values=fill)
        losses, hits, arrived, dropped = [], [], [], jnp.zeros((), jnp.int32)
        for k, module in enumerate(self.mtp, start=1):
            with profile_scope("mtp/combine", "compute"):
                embedded = self.embed(ahead(k - 1, 0))
            stream, h, counts, lost = module(stream, embedded)
            with profile_scope("lm/loss", "compute"):
                total, hit = blocked_cross_entropy(
                    h.reshape(-1, h.shape[-1]),
                    self.head_kernel.astype(self.dtype),
                    ahead(k, -1).reshape(-1), self.cfg.loss_block)
            real = b * (length - k)
            losses.append(total / real)
            hits.append(hit / real)
            arrived.append(counts)
            dropped = dropped + lost
        return (jnp.stack(losses), jnp.stack(hits), jnp.concatenate(arrived),
                dropped)

    def loss_and_aux(self, tokens, labels, train: bool = True):
        """(mean cross-entropy of ``labels`` [B, L], aux).  ``aux`` holds
        ``accuracy`` and, where an expert layer exists, ``counters``:
        scalars a step (assignments per held expert and layer as min,
        mean, max, those dropped, and the rows the expert layers' dispatch
        moved over the places their pools walked).  With
        multi-token-prediction modules (``mtp_depth`` D > 0) the loss is
        ``main + mtp_weight / D x (sum of the depths' losses)``
        (arXiv:2412.19437, eq. 24-25), ``accuracy`` stays the main head's,
        the counters gain ``lm/main_loss``, ``mtp/loss`` (the depths' mean)
        and ``mtp/accuracy``, and the expert counters count the modules'
        expert layers with the others."""
        h, arrived, dropped, stream = self.features(tokens)
        if self.loops > 1:
            return self.looped_loss(h, labels)
        with profile_scope("lm/loss", "compute"):
            total, hits = blocked_cross_entropy(
                h.reshape(-1, h.shape[-1]),
                self.head_kernel.astype(self.dtype), labels.reshape(-1),
                self.cfg.loss_block)
        aux, counters = {"accuracy": hits / labels.size}, {}
        if self.mtp:
            with profile_scope("mtp/module", "compute"):
                further, further_hits, counts, lost = self.mtp_losses(
                    stream, labels)
            counters = {"mtp/loss": jnp.mean(further),
                        "mtp/accuracy": jnp.mean(further_hits)}
            arrived = jnp.concatenate([arrived, counts])
            dropped = dropped + lost
        if arrived.size:
            c = self.cfg
            walked = jnp.sum(places_walked(
                jnp.sum(arrived.reshape(-1, c.experts_held), axis=1),
                c.experts_held, c.expert_rows, c.expert_pool))
            moved = jnp.sum(arrived) - dropped
            arrived = arrived.astype(jnp.float32)
            counters.update({
                "moe/assignments_min": jnp.min(arrived),
                "moe/assignments_mean": jnp.mean(arrived),
                "moe/assignments_max": jnp.max(arrived),
                "moe/dropped": dropped.astype(jnp.float32),
                "moe/pool_fill": moved / walked.astype(jnp.float32)})
        loss = total / labels.size
        if self.mtp:
            counters["lm/main_loss"] = loss
            loss = loss + self.cfg.mtp_weight * counters["mtp/loss"]
        if counters:
            aux["counters"] = counters
        return loss, aux

    def looped_loss(self, streams, labels):
        """The expected-exit loss of a looped stack (arXiv:2510.25741,
        stage I under a uniform prior) over its T normed streams
        [T, B, L, hidden]:

            L = mean_i [ sum_t p^t_i ce^t_i - exit_beta H(p_i) ]

        ``ce^t_i`` the next-token cross-entropy of step t's logits, ``p_i``
        token i's exit distribution (`exit_distribution` of the shared
        gate's logits on the normed streams), ``H`` its entropy, all in
        float32.  ONE blocked pass over the T x tokens rows of the head.
        ``accuracy`` is the last step's; the counters hold each step's
        mean cross-entropy and mean exit mass, the mean entropy and the
        expected loss without the entropy term."""
        loops, tokens = self.loops, labels.size
        with profile_scope("loop/exit", "compute"):
            log_p = exit_distribution(
                self.exit_gate(streams).reshape(loops, tokens))
            p = jnp.exp(log_p)
            entropy = -jnp.sum(p * log_p) / tokens
        with profile_scope("lm/loss", "compute"):
            total, hits, sums = blocked_cross_entropy(
                streams.reshape(loops * tokens, -1),
                self.head_kernel.astype(self.dtype),
                jnp.tile(labels.reshape(-1), loops), self.cfg.loss_block,
                p.reshape(-1), loops)
        expected = total / tokens
        counters = {"lm/main_loss": expected, "loop/exit_entropy": entropy}
        masses = jnp.mean(p, axis=1)
        for t in range(loops):
            counters[f"loop/loss_{t + 1}"] = sums[t] / tokens
            counters[f"loop/exit_mass_{t + 1}"] = masses[t]
        return expected - self.cfg.exit_beta * entropy, {
            "accuracy": hits[-1] / tokens, "counters": counters}


class ExitGate(nn.Module):
    """A looped stack's exit gate: one linear map hidden -> 1 with a bias,
    shared by the loop steps, on the normed streams; logits in float32."""

    @nn.compact
    def __call__(self, h):
        kernel = self.param("kernel", _fan_in, (h.shape[-1], 1))
        bias = self.param("bias", nn.initializers.zeros, (1,))
        return jnp.dot(h.astype(jnp.float32), kernel[:, 0],
                       precision=_HIGHEST) + bias[0]


def exit_distribution(a):
    """log p [T, N] of gate logits a [T, N]: with lambda^t = sigmoid(a^t),
    p^t = lambda^t prod_{j<t} (1 - lambda^j) for t < T and the last step
    takes the rest, p^T = prod_{j<T} (1 - lambda^j).  From `log_sigmoid`
    of a and of -a, so that no log 0 arises; a token's masses sum to 1."""
    stay = jnp.cumsum(jax.nn.log_sigmoid(-a[:-1]), axis=0)   # log prod(1-l)
    before = jnp.concatenate([jnp.zeros_like(a[:1]), stay[:-1]])
    return jnp.concatenate(
        [jax.nn.log_sigmoid(a[:-1]) + before, stay[-1:]])


def blocked_cross_entropy(h, head, labels, block: int, weights=None,
                          groups: int = 1):
    """(sum of cross-entropies, number of argmax hits) over tokens h
    [T, d], ``block`` tokens at a time: a block's float32 logits are the
    most that lives.

    With ``weights`` [T] (float32): (sum of weight x cross-entropy,
    differentiable in the weights too; the hits [groups]; the unweighted
    sums [groups]) of the rows' ``groups`` equal runs (a looped stack's
    steps), each run blocked on its own.

    Differentiated, the one pass over the blocks makes the gradient
    beside the loss (`_blocked_losses`): three head products a block
    and nothing left for the backward pass but a scale.  Only the first
    output carries a gradient: the hits and the groups' sums are
    counters, and their cotangents are dropped."""
    run = h.shape[0] // groups
    block = min(block, run)
    pad = (-run) % block
    rows = lambda a, fill: jnp.pad(
        a.reshape((groups, run) + a.shape[1:]),
        ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 1),
        constant_values=fill).reshape((-1, block) + a.shape[1:])
    total, hits, sums = _blocked_losses(
        rows(h, 0), head, rows(labels.astype(jnp.int32), -1),
        None if weights is None else rows(weights.astype(jnp.float32), 0))
    if weights is None:
        return total, jnp.sum(hits)
    per_group = lambda a: jnp.sum(a.reshape(groups, -1), axis=1)
    return total, per_group(hits), per_group(sums)


def _block_losses(head, h_, y_):
    """(cross-entropy of each row, 0 where its label is negative; argmax
    hits; the logits; their logsumexp) of one block of rows: the block's
    float32 logits live here."""
    logits = jnp.dot(h_, head, preferred_element_type=jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, jnp.maximum(y_, 0)[:, None], axis=-1)[:, 0]
    real = y_ >= 0
    hits = jnp.sum(real & (jnp.argmax(logits, -1) == y_))
    return jnp.where(real, logz - picked, 0.0), hits, logits, logz


@jax.custom_vjp
def _blocked_losses(h, head, y, w):
    """(sum of [weight x] cross-entropy, hits by block, unweighted sums
    by block) of blocked rows h [n, block, d], labels y [n, block] and
    weights w [n, block] or None: one head product a block."""
    def one(_, xs):
        each, hits = _block_losses(head, xs[0], xs[1])[:2]
        return None, _block_sums(each, hits, xs[2])

    _, (totals, hits, sums) = lax.scan(one, None, (h, y, w))
    return jnp.sum(totals), hits, sums


def _block_sums(each, hits, w_):
    """A block's (sum of [weight x] cross-entropy, hits, unweighted sum)."""
    return (jnp.sum(each if w_ is None else w_ * each),
            hits.astype(jnp.float32), jnp.sum(each))


def _blocked_losses_fwd(h, head, y, w):
    """The same pass with the gradient made beside the loss, while a
    block's logits live: g = softmax - onehot, zero on a row whose label
    is negative and times the row's weight, enters ``dh_ = g head^T`` and
    ``dhead += h_^T g`` in the head's dtype (what the MXU makes of a
    float32 operand at default precision); the products add up in
    float32, dhead over the blocks in the head's dtype."""
    def one(dhead, xs):
        h_, y_, w_ = xs
        each, hits, logits, logz = _block_losses(head, h_, y_)
        real = y_ >= 0
        scale = real.astype(jnp.float32) if w is None else jnp.where(
            real, w_, 0.0)
        p = jnp.exp(logits - logz[:, None])
        onehot = y_[:, None] == lax.broadcasted_iota(
            jnp.int32, logits.shape, 1)
        g = (scale[:, None] * jnp.where(onehot, p - 1.0, p)).astype(
            head.dtype)
        dh_ = lax.dot_general(g, head, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
        dhead = dhead + lax.dot_general(
            h_, g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(head.dtype)
        return dhead, _block_sums(each, hits, w_) + (
            dh_.astype(h.dtype), None if w is None else each)

    dhead, (totals, hits, sums, dh, each) = lax.scan(
        one, jnp.zeros_like(head), (h, y, w))
    return (jnp.sum(totals), hits, sums), (dh, dhead, each)


def _blocked_losses_bwd(residuals, cotangents):
    """The cotangent of the total scales what the forward rule made; no
    product and no pass over logits."""
    dh, dhead, each = residuals
    ct = cotangents[0]
    scaled = lambda a: (ct * a.astype(jnp.float32)).astype(a.dtype)
    return (scaled(dh), scaled(dhead), None,
            None if each is None else ct * each)


_blocked_losses.defvjp(_blocked_losses_fwd, _blocked_losses_bwd)
