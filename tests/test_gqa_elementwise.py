"""The elementwise passes around a grouped-query attention core
(`ops/gqa_elementwise.py`): the norm + rotary kernel pair under the Pallas
interpreter against its jnp form, and both jnp forms against the chain
they replaced (`decoder.RMSNorm` then `afmoe.rotary`;
``o * sigmoid(logits)``), forward and through `jax.grad`, and the door of
`ops/dispatch.py`.

Forward, the kernel's arithmetic IS the jnp form's (one function).  Its
last step is ``a * b + c * d``, which LLVM contracts into a fused
multiply-add in one fused loop and not in another (XLA's CPU backend
always allows it, and no flag turns it off; a v5e's vector unit has no
such instruction): an element may differ by the last place of float32
before the rounding to the caller's dtype.  On the chip the two differ
for another reason, which only the chip shows (PERF.md, PR 37): XLA
elides the jnp form's round trip through bf16 between norm and rotary,
the kernel keeps it; `tools/gqa_proj_timing.py` reports both against
float64.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from geomx_tpu.models import afmoe
from geomx_tpu.models.decoder import RMSNorm
from geomx_tpu.ops import dispatch
from geomx_tpu.ops import gqa_elementwise as ge

EPS, THETA = 1e-5, 10000.0
# [B, L]: whole tiles of 128; a tile of 64 and 16 more; one of 256 and 44
SHAPES = {"whole": (2, 128), "ragged": (1, 80), "ragged-2": (1, 300)}


def chain(q, k, q_scale, k_scale, eps, theta):
    """What `GQAMixer` ran before these passes existed."""
    norm = RMSNorm(eps)
    q = norm.apply({"params": {"scale": q_scale}}, q)
    k = norm.apply({"params": {"scale": k_scale}}, k)
    if theta is not None:
        q, k = afmoe.rotary(q, theta), afmoe.rotary(k, theta)
    return q, k


def chain_gated(o, logits):
    return (o * jax.nn.sigmoid(logits)).astype(o.dtype)


def operands(shape, dtype, heads=(32, 4), d=128, seed=0):
    b, length = SHAPES[shape]
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    wide = lambda key, n: jax.random.normal(
        key, (b, length, n, d), jnp.float32).astype(dtype)
    scale = lambda key: 1.0 + 0.1 * jax.random.normal(key, (d,))
    return ((wide(keys[0], heads[0]), wide(keys[1], heads[1]),
             scale(keys[2]), scale(keys[3])),
            (wide(keys[4], heads[0]), wide(keys[5], heads[1])))


def gate_operands(rows, width, dtype, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    o, g = (jax.random.normal(key, (1, rows, width)).astype(dtype)
            for key in keys[:2])
    return o, 2.0 * jax.random.normal(keys[2], (1, rows, width)), g


def ulps_apart(a, b):
    """Elements that differ, and the largest difference in units of the
    dtype's spacing at the arrays' largest magnitude."""
    a32, b32 = (np.asarray(x, np.float32) for x in (a, b))
    spacing = float(jnp.finfo(a.dtype).eps) * np.max(np.abs(b32))
    return int(np.sum(a32 != b32)), float(np.max(np.abs(a32 - b32))) / spacing


def through_the_door(q, k, q_scale, k_scale, theta):
    """What the mixer gets under `dispatch.kernels("interpret")`: the
    kernel pair in a window layer, the jnp form in a global one."""
    with dispatch.kernels("interpret"):
        return dispatch.gqa_norm_rotary(q, k, q_scale, k_scale, EPS, theta)


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("theta", [THETA, None], ids=["window", "global"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_norm_rotary_forward_is_the_jnp_forms_and_the_chains(dtype, theta,
                                                             shape):
    """32 heads on 4, whole and ragged tiles: what the door gives (the
    kernel, interpreted, with rotary; the jnp form without), the jnp form
    and the chain as it stood, forward."""
    (q, k, q_scale, k_scale), _ = operands(shape, dtype)
    got = through_the_door(q, k, q_scale, k_scale, theta)
    want = jax.jit(lambda *a: ge.norm_rotary_ref(*a, EPS, theta))(
        q, k, q_scale, k_scale)
    old = jax.jit(lambda *a: chain(*a, EPS, theta))(q, k, q_scale, k_scale)
    for a, b, c in zip(got, want, old):
        assert a.dtype == b.dtype == c.dtype == dtype
        assert a.shape == b.shape == c.shape
        # the restated form is the chain: -x * s == x * -s to the bit
        np.testing.assert_array_equal(np.asarray(b, np.float32),
                                      np.asarray(c, np.float32))
        differ, ulps = ulps_apart(a, b)
        if theta is None:
            assert differ == 0
        else:               # a * b + c * d: fused or not (the docstring)
            assert ulps <= 1.0 and differ <= (
                a.size // 1000 if dtype == jnp.bfloat16 else a.size)


@pytest.mark.parametrize("theta", [THETA, None], ids=["window", "global"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_norm_rotary_gradients_are_no_further_from_float32_than_the_chains(
        dtype, theta):
    """dq, dk and both scales' gradients through the door (with rotary
    the `custom_vjp`, on a ragged last tile, so the scales' sums must skip
    the rows past the end) and through the jnp form, against `jax.grad` of
    the chain in float32 at `highest`: the largest gap is no larger than
    the chain's own in that dtype (with a tenth of it, and 4e-6 for the
    order of a float32 sum over 9,600 heads and tokens, of room: without
    rotary the chain's scale gradients ARE the float32 ones, summed in
    XLA's order)."""
    (q, k, q_scale, k_scale), cots = operands("ragged-2", dtype)

    def loss(fn):
        def of(*a):
            outs = fn(*a)
            return sum(jnp.sum(o.astype(jnp.float32) * g.astype(jnp.float32))
                       for o, g in zip(outs, cots))
        return jax.jit(jax.grad(of, (0, 1, 2, 3)))

    args = (q, k, q_scale, k_scale)
    with jax.default_matmul_precision("highest"):
        want = loss(lambda *a: chain(*a, EPS, theta))(
            *(x.astype(jnp.float32) for x in args))
    door = loss(lambda *a: through_the_door(*a, theta))(*args)
    form = loss(lambda *a: ge.norm_rotary_ref(*a, EPS, theta))(*args)
    old = loss(lambda *a: chain(*a, EPS, theta))(*args)
    gap = lambda got, ref: float(jnp.max(jnp.abs(
        got.astype(jnp.float32) - ref)) / jnp.max(jnp.abs(ref)))
    for name, g_door, g_form, g_old, ref, arg in zip(
            ("dq", "dk", "dq_scale", "dk_scale"), door, form, old, want,
            args):
        assert g_door.dtype == arg.dtype and g_door.shape == arg.shape
        assert gap(g_door, ref) <= 1.1 * gap(g_old, ref) + 4e-6, name
        assert gap(g_form, ref) <= 1.1 * gap(g_old, ref) + 4e-6, name


@pytest.mark.parametrize("rows,width", [(128, 4096), (80, 1024), (150, 384)],
                         ids=["whole", "ragged", "narrow-columns"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_gate_forward_and_gradients(dtype, rows, width):
    """``(o * sigmoid(logits))`` in o's dtype, the chain's to the bit
    forward; `d_o` in o's dtype and `d_logits` in float32, as JAX's
    transpose of the chain gives them (the gate's kernel pair did not beat
    this form on the chip and went: PERF.md, PR 37)."""
    o, logits, g = gate_operands(rows, width, dtype)
    got = jax.jit(ge.gated_ref)(o, logits)
    assert got.dtype == dtype
    np.testing.assert_array_equal(
        np.asarray(got, np.float32),
        np.asarray(jax.jit(chain_gated)(o, logits), np.float32))

    def grads(fn, o, logits):
        return jax.grad(lambda o, l: jnp.sum(
            fn(o, l).astype(jnp.float32) * g.astype(jnp.float32)),
            (0, 1))(o, logits)

    want = grads(chain_gated, o.astype(jnp.float32), logits)
    form = grads(ge.gated_ref, o, logits)
    old = grads(chain_gated, o, logits)
    gap = lambda got, ref: float(jnp.max(jnp.abs(
        got.astype(jnp.float32) - ref)) / jnp.max(jnp.abs(ref)))
    for g_form, g_old, ref in zip(form, old, want):
        assert g_form.dtype == g_old.dtype
        assert gap(g_form, ref) <= 1.1 * gap(g_old, ref) + 1e-6


def test_the_plan_follows_the_shape():
    """The tile is what VMEM holds of the caller's width and dtype, up to
    `MAX_TILE` and to the tokens there are; no kernel for a head that is
    not whole lane tiles, for another dtype, or for fewer tokens than
    `MIN_TILE`."""
    q, k = (1, 8192, 32, 128), (1, 8192, 4, 128)
    bf16, f32 = jnp.bfloat16, jnp.float32
    assert ge.norm_rotary_plan(q, k, bf16, False).tile == 256
    assert ge.norm_rotary_plan(q, k, bf16).tile == 256
    assert ge.norm_rotary_plan(q, k, f32, False).tile == 256
    assert ge.norm_rotary_plan(q, k, f32).tile == 128
    assert ge.norm_rotary_plan(q, k, f32).vmem_bytes <= ge.VMEM_BUDGET
    assert ge.norm_rotary_plan((1, 80, 32, 128), (1, 80, 4, 128),
                               bf16).tile == 64
    assert ge.norm_rotary_plan((1, 16, 32, 128), (1, 16, 4, 128),
                               bf16).tile == 16
    assert ge.norm_rotary_plan((2, 40, 8, 16), (2, 40, 2, 16), f32) is None
    assert ge.norm_rotary_plan((1, 8, 32, 128), (1, 8, 4, 128), bf16) is None
    assert ge.norm_rotary_plan(q, k, jnp.float16) is None


def _primitives(fn, *args):
    return {eqn.primitive.name for eqn in jax.make_jaxpr(fn)(*args).eqns}


@pytest.mark.parametrize("mode,d,theta,kernel", [
    (None, 128, THETA, False), ("interpret", 128, THETA, True),
    ("interpret", 16, THETA, False), ("interpret", 128, None, False)],
    ids=["no-mode", "head-128", "head-16", "no-rotary"])
def test_the_door_chooses_from_the_mode_and_the_shape(mode, d, theta, kernel):
    """`kernel_mode()` None (a CPU): the jnp form; a mode, rotary and a
    head of whole lane tiles: the kernels; the tiny models' 16-wide heads,
    and a global layer's norm alone: the jnp form under any mode.  Same
    values either way."""
    (q, k, q_scale, k_scale), _ = operands("ragged", jnp.float32,
                                           heads=(8, 2), d=d)
    # a fresh function: a trace is cached by the function, not by the mode
    norm = lambda *a: dispatch.gqa_norm_rotary(*a, EPS, theta)
    if mode is None:
        seen = _primitives(norm, q, k, q_scale, k_scale)
        got = norm(q, k, q_scale, k_scale)
    else:
        with dispatch.kernels(mode):
            seen = _primitives(norm, q, k, q_scale, k_scale)
            got = norm(q, k, q_scale, k_scale)
    assert any(p.startswith("custom_vjp") for p in seen) == kernel
    want = ge.norm_rotary_ref(q, k, q_scale, k_scale, EPS, theta)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-6)


@pytest.mark.parametrize("kind", ["window", "global"])
def test_the_mixer_keeps_its_parameters_and_its_values(kind):
    """A mixer at a head of 128 under `dispatch.kernels("interpret")` (a
    window layer's norm + rotary through the kernels) and through the jnp
    forms: the same parameter tree as before
    (`q_norm/scale`, `k_norm/scale` beside the five products), the same
    output and gradients within float32's roundings."""
    mixer = afmoe.GQAMixer(num_heads=4, num_kv_heads=2, head_dim=128,
                           window=24 if kind == "window" else None,
                           rope=THETA if kind == "window" else None, eps=EPS)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 64, 96))
    params = jax.jit(mixer.init)(jax.random.PRNGKey(1), x)["params"]
    assert sorted(params) == ["gate_kernel", "k_kernel", "k_norm",
                              "out_kernel", "q_kernel", "q_norm", "v_kernel"]
    assert params["q_norm"]["scale"].shape == (128,)
    assert params["k_norm"]["scale"].shape == (128,)
    loss = lambda p: jnp.sum(jnp.square(mixer.apply({"params": p}, x)))
    want = jax.jit(jax.value_and_grad(loss))(params)
    with dispatch.kernels("interpret"):
        got = jax.jit(jax.value_and_grad(loss))(params)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for a, b in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        np.testing.assert_allclose(a, b, rtol=2e-4,
                                   atol=2e-5 * float(jnp.max(jnp.abs(b))))
