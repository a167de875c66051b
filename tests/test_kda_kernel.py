"""The KDA scan's Pallas kernels (`ops/kda_pallas.py`) in interpret mode
against their two oracles: the jnp form (`ops/kda.kda_chunked`, whose
`jax.vjp` is the backward's oracle) and the plain reference's
token-by-token recurrence.  Interpret mode sees neither tiling, VMEM nor
MXU precision: `tests/test_tpu_compile_decoders.py` asks the compiler, and
`tools/kda_timing.py` checks values on the chip.  A file of its own, so
that `--dist loadfile` gives it a worker."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_checks as checks
from benchmark.references import kimi_linear as plain
from geomx_tpu.ops import dispatch, kda_pallas
from geomx_tpu.ops.kda import kda_chunked
from geomx_tpu.ops.kda_pallas import (kda_plan, kda_scan,
                                      kda_scan_bwd, kda_scan_fwd)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "kda_timing", os.path.join(ROOT, "tools", "kda_timing.py"))
kda_timing = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(kda_timing)

NX = checks.NX
DK, DV = 32, 16
WEIGHT = jnp.cos(jnp.arange(float(DV)))


def inputs(seed, length, decay, h=2, shift=0.0):
    """Heads-major [1, H, L, d], as `KDAMixer` writes them."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (1, h, length, DK))) * DK ** -0.5
    k = unit(jax.random.normal(ks[1], (1, h, length, DK)))
    v = jax.random.normal(ks[2], (1, h, length, DV))
    g = -decay * jax.random.uniform(ks[3], (1, h, length, DK)) - shift
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, h, length)))
    return q, k, v, g, beta


def value_and_grads(fn, args):
    """o and the gradients of all five inputs under a fixed weighting."""
    return checks.value_and_gradients(fn, args, range(5), WEIGHT)


def recurrence(q, k, v, g, beta):
    major = lambda x: jnp.swapaxes(x, 1, 2)
    return major(plain.delta_rule_recurrence(
        NX, *map(major, (q, k, v, g, beta)), block=8))


def assert_close(got, want, rel, what):
    """Within ``rel`` of the oracle's largest magnitude."""
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=rel * scale, rtol=0, err_msg=what)


@pytest.mark.parametrize("length,chunk,decay", [
    (128, 64, 0.07),    # whole chunks, a trained layer's decay
    (150, 64, 1.0),     # no multiple of the chunk: the tail is padded
    (37, 16, 5.0),      # exp(-5) a token, 16 chunks a grid step
])
def test_float32_kernel_equals_both_oracles(length, chunk, decay):
    """Float32 operands: in interpret mode every product is a float32
    product, so kernel and jnp form differ by the order of their sums
    alone: 2e-6 of the largest value for the output (what the jnp form is
    held to against the recurrence) and 2e-5 for the gradients (sums over
    up to 150 tokens of terms that cancel)."""
    args = inputs(length, length, decay)
    sub = min(16, chunk)
    kernel = lambda *a: kda_scan(*a, chunk, sub, jnp.float32, True)
    o, grads = value_and_grads(kernel, args)
    for name, oracle in (
            ("kda_chunked", lambda *a: kda_chunked(*a, chunk=chunk, sub=sub)),
            ("recurrence", recurrence)):
        want_o, want = value_and_grads(oracle, args)
        assert_close(o, want_o, 2e-6, f"o against {name}")
        for which, got, ref in zip("q k v g beta".split(), grads, want):
            assert_close(got, ref, 2e-5, f"d{which} against {name}")


def test_bf16_operands_stay_within_the_jnp_forms_rounding():
    """bf16 operands (the chip cell's): kernel and jnp form round the same
    operands to bf16 in different groupings, so they are compared through
    the float32 oracle: the kernel's largest gap from it is at most twice
    the jnp form's own (measured 0.8-1.8 x: the largest of ~4,000
    roundings; the kernel also rounds the backward's cotangent operands to
    bf16, as the chip's matrix unit does at default precision), and under
    2% outright."""
    args = inputs(7, 128, 0.07)
    args = args[:2] + (args[2].astype(jnp.bfloat16),) + args[3:]
    exact = tuple(x.astype(jnp.float32) for x in args)
    want_o, want = value_and_grads(
        lambda *a: kda_chunked(*a, chunk=64, sub=16), exact)
    got = value_and_grads(
        lambda *a: kda_scan(*a, 64, 16, jnp.bfloat16, True), args)
    ref = value_and_grads(
        lambda *a: kda_chunked(*a, chunk=64, sub=16, dtype=jnp.bfloat16),
        args)
    gap = lambda x, w: float(jnp.max(jnp.abs(x.astype(jnp.float32) - w))
                             / jnp.max(jnp.abs(w)))
    pairs = [(got[0], ref[0], want_o)] + list(zip(got[1], ref[1], want))
    for name, (mine, jnps, oracle) in zip("o q k v g beta".split(), pairs):
        assert gap(mine, oracle) <= max(2 * gap(jnps, oracle), 1e-3), name
        assert gap(mine, oracle) < 0.02, name
    assert got[1][2].dtype == jnp.bfloat16          # dv in v's dtype


def one_pass(a, b, dims, dtype, exact=False):
    """`_dot` with the float32 products rounded to the caller's dtype."""
    return jax.lax.dot_general(a.astype(dtype), b.astype(dtype), dims,
                               preferred_element_type=jnp.float32)


@pytest.mark.parametrize("dot,holds", [(None, True), (one_pass, False)])
def test_a_bf16_callers_float32_pieces_are_float32(monkeypatch, dot, holds):
    """What `kda_chunked` keeps in float32 for a bf16 caller, the kernel
    keeps too: the scores within blocks of ``sub`` tokens and the
    triangular inverse, against float64 (`tools/kda_timing.pieces_gap`,
    which prints the same numbers on the chip).  2e-6 of the largest
    value: sums of 128 float32 terms read 5e-7, the inverse's ten products
    2e-8.  The second case is the check's own check: with those products
    rounded to bf16 the same numbers read 1e-3 and more, so a kernel that
    loses the precision fails here, where the end-to-end gaps (0.4% of
    bf16 rounding either way) cannot tell."""
    if dot is not None:
        monkeypatch.setattr(kda_pallas, "_dot", dot)
    q, k, _, g, beta = inputs(13, 64, 0.14, h=2)
    stacked = lambda x: x[0].reshape(128, -1)
    one = (stacked(q), stacked(k), stacked(g), stacked(beta[..., None]).T,
           64, 16)
    gaps = kda_timing.pieces_gap(
        kda_timing.chunk_pieces(*one, jnp.bfloat16, interpret=True), *one)
    assert set(gaps) == {"scores_qk", "scores_kk", "inverse"}
    for name, gap in gaps.items():
        assert (gap <= 2e-6) if holds else (gap >= 1e-3), (name, gap)


def dots_of(jaxpr):
    """Every dot_general under a jaxpr, kernels' bodies included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from dots_of(inner)


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_a_bf16_callers_float32_products_ask_for_highest(direction):
    """The chip's matrix unit rounds a float32 product's operands to bf16
    unless the product asks for `HIGHEST`, and interpret mode does not: so
    the kernels' programs are read.  For a bf16 caller every product has
    bf16 operands, or float32 operands at `HIGHEST` (forward: four levels
    under ``sub`` and the inverse's ten a pack; backward: those again, the
    levels' two cotangent products each and the inverse's two)."""
    q, k, v, g, beta = inputs(1, 128, 0.07)
    v = v.astype(jnp.bfloat16)
    kw = dict(chunk=64, sub=16, dtype=jnp.bfloat16, interpret=True)
    if direction == "forward":
        program = jax.make_jaxpr(lambda *a: kda_scan_fwd(*a, **kw))(
            q, k, v, g, beta)
        exact = 14
    else:
        states = jnp.zeros((1, 2, 2, DV, DK), jnp.float32)
        program = jax.make_jaxpr(lambda *a: kda_scan_bwd(*a, **kw))(
            q, k, v, g, beta, states, v)
        exact = 14 + 8 + 2
    dots = list(dots_of(program.jaxpr))
    kinds = {tuple(str(x.aval.dtype) for x in eqn.invars) for eqn in dots}
    assert kinds == {("bfloat16",) * 2, ("float32",) * 2}
    highest = (jax.lax.Precision.HIGHEST,) * 2
    wide = [eqn for eqn in dots if str(eqn.invars[0].aval.dtype) == "float32"]
    assert len(wide) == exact
    assert all(eqn.params["precision"] == highest for eqn in wide)


def test_strong_decay_overflows_nowhere():
    """exp(-20) a token: only differences <= 0 are exponentiated, so
    nothing overflows forward or backward, and output and gradients are
    the recurrence's.  (dg is ~2e-10 here and the jnp form's is rounding
    noise, 127% off: its reverse cumulative sum cancels terms of order
    one.  The kernel's comes from the same differences as the forward and
    holds 2e-5.)"""
    args = inputs(3, 64, 0.0, h=1, shift=20.0)
    o, grads = value_and_grads(
        lambda *a: kda_scan(*a, 64, 16, jnp.float32, True), args)
    assert bool(jnp.all(jnp.isfinite(o)))
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in grads)
    want_o, want = value_and_grads(recurrence, args)
    assert_close(o, want_o, 2e-6, "o")
    for which, got, ref in zip("q k v g beta".split(), grads, want):
        assert_close(got, ref, 2e-5, f"d{which}")


def test_heads_a_step_do_not_change_a_result(monkeypatch):
    """One head a grid step, or two stacked into one chunk computation
    (`_pack`: the other head's rows are masked out of every pair, so a
    head's sums gain only exact zeros, in another order): outputs, saved
    states and gradients agree to 2e-6 of their largest value."""
    q, k, v, g, beta = inputs(11, 320, 1.0)
    do = jax.random.normal(jax.random.PRNGKey(5), (1, 2, 320, DV))
    results = []
    for heads in (1, 2):
        monkeypatch.setattr(kda_pallas, "MAX_HEADS", heads)
        assert kda_plan(320, 2, DK, DV, 32, jnp.float32).heads == heads
        kw = dict(chunk=32, sub=16, interpret=True)
        o, states = jax.jit(lambda *a: kda_scan_fwd(
            *a, save_states=True, **kw))(q, k, v, g, beta)
        grads = jax.jit(lambda *a: kda_scan_bwd(*a, **kw))(
            q, k, v, g, beta, states, do)
        results.append((o, states) + tuple(grads))
    for name, one, two in zip("o states dq dk dv dg dbeta".split(),
                              *results):
        assert_close(one, two, 2e-6, name)
    # 10 chunks in two grid steps of 8: the state crosses a step
    assert results[0][1].shape == (1, 2, 16, DV, DK)


def test_the_door_picks_kernel_or_jnp_form_from_the_mode():
    args = inputs(2, 64, 1.0)
    door = lambda: jax.jit(lambda *a: dispatch.kda(*a, chunk=32, sub=16))
    plain_o = door()(*args)
    np.testing.assert_array_equal(
        np.asarray(plain_o),
        np.asarray(jax.jit(lambda *a: kda_chunked(*a, chunk=32, sub=16))(
            *args)))
    with dispatch.kernels("interpret"):
        kernel_o = door()(*args)
    np.testing.assert_array_equal(
        np.asarray(kernel_o),
        np.asarray(jax.jit(lambda *a: kda_scan(
            *a, 32, 16, jnp.float32, True))(*args)))
    assert_close(kernel_o, plain_o, 2e-6, "door")


@pytest.mark.parametrize("length,heads,want", [
    (8192, 32, (4, 4)),     # the chip cell: 256 grid steps a sequence
    (100, 3, (3, 2)),       # 3 heads: all in a step; two chunks hold 100
    (16, 2, (2, 1)),
])
def test_plan_reads_shapes_alone(length, heads, want):
    plan = kda_plan(length, heads, 128, 128, 64, jnp.bfloat16)
    assert (plan.heads, plan.chunks) == want
    assert plan.vmem_bytes <= 24 * 2 ** 20
