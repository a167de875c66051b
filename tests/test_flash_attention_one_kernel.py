"""ONE backward kernel past one block pair (`ops/flash_attention.py`, "The
backward's schedule" in docs/kernels.md): its gradients against the dq
kernel's and the dk/dv kernel's and against the dense form, in Pallas
interpret mode; the plan's rule for it, ONE bound on what its call asks
Mosaic for; the timing tool's grouped shapes; the span that says which
form ran.  A file of its own beside ``test_flash_attention.py``, whose
helpers it takes: together they would pass a worker's 180 s
(docs/testing.md)."""

import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from geomx_tpu.ops.flash_attention import fused_attention
from test_flash_attention import BF16_TOLERANCE, dense_reference, dense_vjp


def _two_kernel_backward(monkeypatch):
    """`flash_attention_bwd` as it is where ONE kernel of several block
    pairs may ask for no VMEM: the dq kernel and the dk/dv kernel.  The
    constant is read while a call is traced, so the jitted function's own
    cache is left alone."""
    # `geomx_tpu.ops.flash_attention` the attribute is the function
    module = importlib.import_module("geomx_tpu.ops.flash_attention")
    monkeypatch.setattr(module, "ONE_KERNEL_VMEM", 0)
    return module.flash_attention_bwd.__wrapped__


# (L, H, D, Dv, given block, key/value heads, window): several block pairs
# each, causal
ONE_KERNEL = {
    "latent-192-128": (96, 2, 192, 128, 32, None, None),
    "latent-256-256": (128, 2, 256, 256, 32, None, None),
    "ragged": (100, 2, 64, 64, 32, None, None),      # a padded last block
    "band": (160, 2, 64, 64, 32, None, 40),          # pairs under the band
    "band-wider-than-a-block": (160, 2, 64, 64, 32, None, 70),
    "grouped": (96, 4, 32, 32, 32, 2, None),         # whole groups a step
    "grouped-8-on-1-band": (128, 8, 16, 16, 32, 1, 50),
    # the grouped cells' form, 128-wide heads that all read one key/value
    # head: six blocks with no band, and under one of 2.2 blocks
    "grouped-8-on-1-of-128": (192, 8, 128, 128, 32, 1, None),
    "grouped-8-on-1-of-128-band": (192, 8, 128, 128, 32, 1, 70),
    "plans-own-blocks": (640, 4, 32, 32, None, None, None),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(ONE_KERNEL))
def test_one_backward_kernel_equals_the_two_kernels_and_the_dense_form(
        case, dtype, monkeypatch):
    """Past one block pair a causal call's backward is ONE kernel that
    keeps dq^T for every q block: the same products on the same operands
    in the same order as the dq kernel and the dk/dv kernel, so the three
    gradients are theirs to float32 rounding, and the dense form's to the
    dtype's."""
    from geomx_tpu.ops.flash_attention import (attention_plan,
                                               flash_attention_bwd,
                                               flash_attention_with_lse)
    length, h, d, dv, block, kv_heads, window = ONE_KERNEL[case]
    rng = np.random.RandomState(46)
    draw = lambda heads, e: jnp.asarray(rng.normal(
        size=(2, length, heads, e)).astype(np.float32)).astype(dtype)
    kv = kv_heads or h
    q, k, v, g = draw(h, d), draw(kv, d), draw(kv, dv), draw(h, dv)
    given = dict(causal=True, block_q=block, block_k=block, interpret=True,
                 window=window)
    plan = attention_plan(length, length, h, d, dv, dtype, True, block, block,
                          kv_heads=kv)
    nq = -(-length // plan.block_q)
    assert plan.fused_backward and nq > 1
    assert plan.resident_bytes == 4 * nq * plan.block_q * d * max(
        plan.heads, h // kv)
    out, lse = flash_attention_with_lse(q, k, v, **given)
    one = flash_attention_bwd(q, k, v, out, lse, g, **given)
    two = jax.jit(lambda *a: _two_kernel_backward(monkeypatch)(*a, **given))(
        q, k, v, out, lse, g)
    f32 = lambda x: np.asarray(x, np.float32)
    _, dense = dense_vjp(lambda q, k, v: dense_reference(
        q, k, v, True, window), *map(jnp.asarray, map(f32, (g, q, k, v))))
    # a float32 result is the same sums; a bf16 one their rounding, which
    # may fall either way where two float32 sums differ in the last place
    same = 2.0 ** -21 if dtype == jnp.float32 else 2.0 ** -8
    close = 3e-5 if dtype == jnp.float32 else BF16_TOLERANCE
    for got, other, want in zip(one, two, dense):
        assert got.dtype == dtype and got.shape == want.shape
        top = np.abs(f32(want)).max()
        assert np.abs(f32(got) - f32(other)).max() <= same * top
        assert np.abs(f32(got) - f32(want)).max() <= close * max(top, 1.0)


def test_two_backward_kernels_where_the_constant_says_so(monkeypatch):
    """The switch the tests and the timing tool use is the plan's own
    constant: with no room for dq^T the same call lowers to the dq kernel
    and the dk/dv kernel, with room to `flash_attention_bwd` alone."""
    from jax import export as jax_export

    from geomx_tpu.ops.flash_attention import flash_attention_bwd
    x = jax.ShapeDtypeStruct((1, 256, 2, 64), jnp.float32)
    args = (x, x, x, x, jax.ShapeDtypeStruct((1, 2, 256), jnp.float32), x)

    def kernels(backward):
        fn = lambda *a: backward(*a, causal=True, block_q=128, block_k=128)
        text = jax_export.export(jax.jit(fn), platforms=("tpu",))(
            *args).mlir_module()
        return set(re.findall(r'kernel_name = "(\w+)"', text))

    assert kernels(flash_attention_bwd.__wrapped__) == {"flash_attention_bwd"}
    assert kernels(_two_kernel_backward(monkeypatch)) == {
        "flash_attention_bwd_dq", "flash_attention_bwd_dkv"}


# the cells' shapes as `attention_plan` takes them (q length, kv length,
# heads, d, dv, dtype, causal, block_q, block_k, kv heads) and whether ONE
# backward kernel runs, with the dq^T it keeps; a third entry: the band a
# window layer of that cell puts on the same call
PAST_THE_BOUND = "the one kernel would ask for more than ONE_KERNEL_VMEM"
_GROUPED = (32, 128, 128, jnp.bfloat16, True, None, None, 4)
ONE_KERNEL_RULE = {
    "glm-16k-20x256": ((16384, 16384, 20, 256, 256, jnp.bfloat16, True),
                       16 * 2 ** 20),
    "kimi-8k-32x192-128": ((8192, 8192, 32, 192, 128, jnp.bfloat16, True),
                           12 * 2 ** 20),
    "nemotron-8k-4-on-1": ((8192, 8192, 4, 128, 128, jnp.bfloat16, True,
                            None, None, 1), 16 * 2 ** 20),
    "not-causal": ((16384, 16384, 20, 256, 256, jnp.bfloat16, False), None),
    "unequal-given-blocks": ((16384, 16384, 20, 256, 256, jnp.bfloat16, True,
                              512, 256), None),
    "q-shorter-than-kv": ((8192, 16384, 20, 256, 256, jnp.bfloat16, True),
                          None),
    # twice the GLM length asks for 57.5 MiB in all
    "glm-32k": ((32768, 32768, 20, 256, 256, jnp.bfloat16, True),
                32 * 2 ** 20),
    # the grouped cells, whose kernel takes the group of eight: 60.5 and
    # 92.5 MiB in all, in a global layer and under a window layer's band
    "trinity-8k-32-on-4": ((8192, 8192) + _GROUPED, 32 * 2 ** 20),
    "mellum-16k-32-on-4": ((16384, 16384) + _GROUPED, 64 * 2 ** 20),
    "trinity-8k-32-on-4-window": ((8192, 8192) + _GROUPED, 32 * 2 ** 20,
                                  2048),
    "mellum-16k-32-on-4-window": ((16384, 16384) + _GROUPED, 64 * 2 ** 20,
                                  1024),
    # float32 at 192/128 streams 17.8 MB in one kernel: 44.3 MiB in all
    "kimi-f32": ((8192, 8192, 32, 192, 128, jnp.float32, True),
                 12 * 2 ** 20),
    # what the bound keeps out: the group of eight at 32,768 (128 MiB of
    # dq^T), and at 16,384 with float32 operands (100.5 MiB in all)
    "eight-on-one-32k": ((32768, 32768) + _GROUPED, PAST_THE_BOUND),
    "mellum-16k-f32": ((16384, 16384, 32, 128, 128, jnp.float32, True, None,
                        None, 4), PAST_THE_BOUND),
}


@pytest.mark.parametrize("case", sorted(ONE_KERNEL_RULE))
def test_one_backward_kernel_rule_reads_the_shapes_alone(case, monkeypatch):
    """ONE bound decides, on what the one kernel's call asks Mosaic for in
    all (`vmem_limit_bytes`: streamed + dq^T + headroom); a band chooses
    no size; and where the walk itself does not fit the form (not causal,
    unequal blocks or lengths) no bound lets the call in."""
    module = importlib.import_module("geomx_tpu.ops.flash_attention")
    args, resident, *window = ONE_KERNEL_RULE[case]
    plan = module.attention_plan(*args)
    one = resident not in (None, PAST_THE_BOUND)
    assert plan.fused_backward == one
    assert plan.resident_bytes == (resident if one else 0)
    if one:
        assert (resident < plan.vmem_bytes
                <= module.ONE_KERNEL_VMEM - module._VMEM_HEADROOM)
    if window:
        heads, d, dv, dtype = args[2:6]
        q, k, v = (jax.ShapeDtypeStruct((1, args[0], h, e), dtype)
                   for h, e in ((heads, d), (args[9], d), (args[9], dv)))
        call = module._prepare(q, k, v, True, window[0], None, None)
        assert call.plan == plan and call.window == window[0]
    monkeypatch.setattr(module, "ONE_KERNEL_VMEM", 2 ** 40)
    assert module.attention_plan(*args).fused_backward == (
        resident is not None)


@pytest.mark.parametrize("name,resident", [
    ("trinity-global", 32 * 2 ** 20), ("mellum-global", 64 * 2 ** 20),
    ("trinity-window", 32 * 2 ** 20), ("mellum-window", 64 * 2 ** 20)])
def test_timing_tool_names_the_grouped_cells_shapes(name, resident,
                                                    monkeypatch):
    """`tools/flash_attention_timing.py trinity-global mellum-global
    trinity-window mellum-window [--set ONE_KERNEL_VMEM=<bytes>]` (a band
    chooses no size) is how the bound is defended or moved: as shipped
    those shapes take ONE backward kernel that keeps the group's dq^T
    (eight heads on one key/value head) with the forward still at four
    heads a step, and two kernels when `--set` puts the bound at 0."""
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools"))
    import flash_attention_timing as tool
    module = importlib.import_module("geomx_tpu.ops.flash_attention")
    b, length, h, d, dv, causal, kv = tool.NAMED[name][:7]
    assert (b, h, kv, d, dv, causal) == (1, 32, 4, 128, 128, True)
    assert tool.NAMED[name][7:] == {"trinity-window": (2048,),
                                    "mellum-window": (1024,)}.get(name, ())
    plan = lambda: module.attention_plan(length, length, h, d, dv,
                                         jnp.bfloat16, causal, kv_heads=kv)
    assert plan().fused_backward and plan().resident_bytes == resident
    assert plan().heads == 4
    # `--set` asks for the attribute by name, as this does
    monkeypatch.setattr(module, "ONE_KERNEL_VMEM", 0)
    assert not plan().fused_backward and plan().resident_bytes == 0
    assert plan()[:3] == (512, 512, 4)


def test_backward_span_says_which_form_ran():
    """The `attn/core` span of a backward call carries the plan's form and
    the dq^T it keeps, where a trace's reader finds them without the
    code."""
    from geomx_tpu.utils.profiler import get_profiler
    q = jnp.ones((1, 1024, 2, 64), jnp.float32)
    prof = get_profiler()
    prof.reset()
    prof.set_state(True)
    try:
        for causal in (True, False):    # 2 x 2 blocks of 512
            jax.jit(jax.grad(lambda q: jnp.sum(fused_attention(
                q, q, q, causal, True)))).lower(q)
        # the grouped-query cells' global and window layers, traced only
        for length, window in ((8192, None), (8192, 2048), (16384, 1024)):
            q, kv = (jax.ShapeDtypeStruct((1, length, h, 128), jnp.bfloat16)
                     for h in (32, 4))
            jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(fused_attention(
                q, k, v, True, True, window).astype(jnp.float32))))(q, kv, kv)
    finally:
        prof.set_state(False)
    spans = [e["args"] for e in prof._events
             if e.get("name") == "attn/core" and e.get("args")]
    prof.reset()
    assert spans == [
        {"backward_kernels": "one", "resident_bytes": 4 * 1024 * 2 * 64},
        {"backward_kernels": "two", "resident_bytes": 0},
        {"backward_kernels": "one", "resident_bytes": 32 * 2 ** 20},
        {"backward_kernels": "one", "resident_bytes": 32 * 2 ** 20},
        {"backward_kernels": "one", "resident_bytes": 64 * 2 ** 20}]
