"""Ask the TPU's compiler, without a TPU (see
``test_tpu_compile_engine.py``): the attention kernels, at the chip
benchmark's own calls and at the shapes that once failed on the chip."""

import functools
import re

import jax
import jax.numpy as jnp
import pytest

import tpu_compile_checks as checks
from tpu_compile_checks import f32


def _flash_fwd(L, dtype):
    from geomx_tpu.ops import flash_attention
    qkv = jax.ShapeDtypeStruct((2, L, 4, 64), dtype)
    return functools.partial(flash_attention, causal=True), [qkv] * 3


def _flash_bwd(L):
    from geomx_tpu.ops import flash_attention_bwd
    qkv = f32(2, L, 4, 64)
    return (functools.partial(flash_attention_bwd, causal=True),
            [qkv, qkv, qkv, qkv, f32(2, 4, L), qkv])


def _latent_fwd(L):
    """Latent attention's head sizes: 192-wide q and k, 128-wide v."""
    from geomx_tpu.ops import flash_attention
    bf16 = lambda d: jax.ShapeDtypeStruct((1, L, 8, d), jnp.bfloat16)
    return (functools.partial(flash_attention, causal=True),
            [bf16(192), bf16(192), bf16(128)])


def _latent_bwd(L):
    from geomx_tpu.ops import flash_attention_bwd
    qk, v = f32(1, L, 8, 192), f32(1, L, 8, 128)
    return (functools.partial(flash_attention_bwd, causal=True),
            [qk, qk, v, v, f32(1, 8, L), v])


def _cell_attention(which, direction):
    """The chip benchmark's own attention calls, bf16: a BERT-large layer
    (16 x 512 x 16 x 64, four heads a step, one backward kernel) and one
    sequence of the decoder's latent attention (8,192 x 32 x 192/128,
    causal: 136 block pairs of 512, one backward kernel that keeps 12 MiB
    of dq^T); one sequence of
    the second decoder's grouped-query attention (8,192 x 32 query heads
    on 4 key/value heads of 128), in a window layer (a band of 2,048 keys:
    70 pairs) and in a global one; one sequence of the third decoder's
    share of its attention layer (four query heads on the one key/value
    head they read); one sequence of the fourth decoder's (16,384 x 32 on
    4 of 128) in a window layer (a band of 1,024 keys, two blocks wide)
    and in a full one (528 block pairs); one sequence of the fifth
    decoder's latent attention (16,384 x 20 x 256/256 causal: one head a
    step at blocks of 512, 528 block pairs, one backward kernel that keeps
    16 MiB of dq^T).  The grouped-query cells' one backward kernel takes
    the group of eight and keeps 32 and 64 MiB of dq^T, under a band as
    without one.  One sequence of the looped decoder's full attention
    (8,192 x 16 on 16 of 128 causal, 24 applications a step: two heads a
    step, one backward kernel that keeps 8 MiB of dq^T)."""
    from geomx_tpu.ops import flash_attention_bwd, flash_attention_with_lse
    b, L, h, kv, d, dv, causal, window = {
        "bert": (16, 512, 16, 16, 64, 64, False, None),
        "latent": (1, 8192, 32, 32, 192, 128, True, None),
        "window": (1, 8192, 32, 4, 128, 128, True, 2048),
        "global": (1, 8192, 32, 4, 128, 128, True, None),
        "share": (1, 8192, 4, 1, 128, 128, True, None),
        "window-16k": (1, 16384, 32, 4, 128, 128, True, 1024),
        "global-16k": (1, 16384, 32, 4, 128, 128, True, None),
        "latent-256": (1, 16384, 20, 20, 256, 256, True, None),
        "equal-16": (1, 8192, 16, 16, 128, 128, True, None)}[which]
    bf16 = lambda heads, e: jax.ShapeDtypeStruct((b, L, heads, e),
                                                 jnp.bfloat16)
    if direction == "forward":
        return (functools.partial(flash_attention_with_lse, causal=causal,
                                  window=window),
                [bf16(h, d), bf16(kv, d), bf16(kv, dv)])
    return (functools.partial(flash_attention_bwd, causal=causal,
                              window=window),
            [bf16(h, d), bf16(kv, d), bf16(kv, dv), bf16(h, dv),
             f32(b, h, L), bf16(h, dv)])


def _grouped_narrow():
    """Grouped heads narrower than a lane tile (8 on 2 of 64, float32, a
    band of 300 keys over 1,024): a query head and its key/value head sit
    differently in their tiles, so the kernels slice the heads' own
    columns."""
    from geomx_tpu.ops import flash_attention_bwd
    q, kv = f32(1, 1024, 8, 64), f32(1, 1024, 2, 64)
    return (functools.partial(flash_attention_bwd, causal=True, window=300),
            [q, kv, kv, q, f32(1, 8, 1024), q])


def _ring_hop(L):
    from geomx_tpu.parallel._fused_block import _hop_pallas
    qkv, ml = f32(8, L, 64), f32(8, L)
    return ((lambda q, k, v, m, l, o: _hop_pallas(
        q, k, v, m, l, o, 0.125, True, 128, False)),
        [qkv, qkv, qkv, ml, ml, qkv])


CASES = {
    "flash_attention-f32-L100": lambda: _flash_fwd(100, jnp.float32),
    "flash_attention-bf16-L1024": lambda: _flash_fwd(1024, jnp.bfloat16),
    "flash_attention-bf16-L8192": lambda: _flash_fwd(8192, jnp.bfloat16),
    "flash_attention_bwd-L100": lambda: _flash_bwd(100),
    "flash_attention_bwd-L8192": lambda: _flash_bwd(8192),
    "flash_attention-latent-192-128-L8192": lambda: _latent_fwd(8192),
    "flash_attention_bwd-latent-192-128-L8192": lambda: _latent_bwd(8192),
    "flash_attention_bwd-latent-192-128-L100": lambda: _latent_bwd(100),
    "flash_attention-bf16-bert-layer": lambda: _cell_attention(
        "bert", "forward"),
    "flash_attention_bwd-bf16-bert-layer": lambda: _cell_attention(
        "bert", "backward"),
    "flash_attention-bf16-latent-sequence": lambda: _cell_attention(
        "latent", "forward"),
    "flash_attention_bwd-bf16-latent-sequence": lambda: _cell_attention(
        "latent", "backward"),
    "flash_attention-bf16-grouped-window-sequence": lambda: _cell_attention(
        "window", "forward"),
    "flash_attention_bwd-bf16-grouped-window-sequence":
        lambda: _cell_attention("window", "backward"),
    "flash_attention-bf16-grouped-global-sequence": lambda: _cell_attention(
        "global", "forward"),
    "flash_attention_bwd-bf16-grouped-global-sequence":
        lambda: _cell_attention("global", "backward"),
    "flash_attention-bf16-four-on-one-sequence": lambda: _cell_attention(
        "share", "forward"),
    "flash_attention_bwd-bf16-four-on-one-sequence":
        lambda: _cell_attention("share", "backward"),
    "flash_attention-bf16-grouped-window-1024-of-16k": lambda:
        _cell_attention("window-16k", "forward"),
    "flash_attention_bwd-bf16-grouped-window-1024-of-16k": lambda:
        _cell_attention("window-16k", "backward"),
    "flash_attention-bf16-grouped-global-16k": lambda: _cell_attention(
        "global-16k", "forward"),
    "flash_attention_bwd-bf16-grouped-global-16k": lambda: _cell_attention(
        "global-16k", "backward"),
    "flash_attention-bf16-latent-256-wide-16k": lambda: _cell_attention(
        "latent-256", "forward"),
    "flash_attention_bwd-bf16-latent-256-wide-16k": lambda: _cell_attention(
        "latent-256", "backward"),
    "flash_attention-bf16-equal-16-of-128-sequence": lambda: _cell_attention(
        "equal-16", "forward"),
    "flash_attention_bwd-bf16-equal-16-of-128-sequence": lambda:
        _cell_attention("equal-16", "backward"),
    "flash_attention_bwd-f32-grouped-64-wide": lambda: _grouped_narrow(),
    "fused_ring_hop-L1024": lambda: _ring_hop(1024),
    "fused_ring_hop-L2048": lambda: _ring_hop(2048),   # 8,192 over 4 chips
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_v5e_compiler_accepts(chip, case):
    assert "tpu_custom_call" in checks.compiled_text(chip, *CASES[case]())


@pytest.mark.parametrize("which,want", [
    ("bert", {"flash_attention_fwd", "flash_attention_bwd"}),
    ("latent", {"flash_attention_fwd", "flash_attention_bwd"}),
    ("latent-256", {"flash_attention_fwd", "flash_attention_bwd"}),
    ("global", {"flash_attention_fwd", "flash_attention_bwd"}),
    ("equal-16", {"flash_attention_fwd", "flash_attention_bwd"}),
])
def test_attention_kernels_carry_the_name_the_benchmark_reads(chip, which,
                                                              want):
    """`flash_attn_roofline_pct` finds the kernels by the prefix
    `flash_attention` of their instruction names
    (benchmark/layer_metrics/flash_attn_roofline_pct.PREFIXES): a kernel
    under another name would leave the share's divisor short."""
    calls = []
    for direction in ("forward", "backward"):
        calls += checks.kernel_calls(checks.compiled_text(
            chip, *_cell_attention(which, direction)))
    assert {c.split(".")[0] for c in calls} == want, calls
    assert all(c.startswith("flash_attention") for c in calls)


@pytest.mark.parametrize("which,asked_mib", [
    ("latent", 38.75), ("share", 41.5), ("latent-256", 41.5),
    ("global", 60.5), ("window", 60.5), ("global-16k", 92.5),
    ("window-16k", 92.5), ("equal-16", 33.5)])
def test_one_backward_kernel_asks_for_its_dq_in_vmem(chip, which, asked_mib):
    """Past one block pair the one backward kernel keeps dq^T for every q
    block, more than the 16 MiB Mosaic gives unasked: the call has to
    raise the limit over the plan's bytes (two equal heads of 128 at 8,192
    keep 8 MiB, half of it), and the chip's compiler has to
    take it (the instruction's `scoped_memory_configs`), up to the 92.5 of
    the core's 128 MiB that the group of eight asks for at 16,384; the
    backward is that one kernel, in a window layer as in a global one."""
    from geomx_tpu.ops.flash_attention import (_VMEM_HEADROOM,
                                               ONE_KERNEL_VMEM,
                                               attention_plan)
    fn, shapes = _cell_attention(which, "backward")
    q, k, v = shapes[:3]
    plan = attention_plan(q.shape[1], k.shape[1], q.shape[2], q.shape[3],
                          v.shape[3], q.dtype, True, kv_heads=k.shape[2])
    assert plan.fused_backward and plan.resident_bytes >= 8 * 2 ** 20
    text = checks.compiled_text(chip, fn, shapes)
    assert [c.split(".")[0] for c in checks.kernel_calls(text)] == [
        "flash_attention_bwd"]
    call, = re.findall(r"%flash_attention_bwd[\w.]* = [^\n]*", text)
    asked, = re.findall(r'"scoped_memory_configs":\[\{[^}]*"size":"(\d+)"',
                        call)
    assert int(asked) == plan.vmem_bytes + _VMEM_HEADROOM <= ONE_KERNEL_VMEM
    assert int(asked) == asked_mib * 2 ** 20



# (q length, kv length, heads, d, dv, key/value heads) -> (block_q, block_k,
# heads a step, one backward kernel, VMEM bytes): `attention_plan`'s answer
# for every shape an accepted cell calls `fused_attention` with, bf16, as
# the parent of PR 45 gave them, and the fifth decoder's beside them; since
# PR 46 a sixth number where one backward kernel runs, the dq^T it keeps
# (of the VMEM bytes, which are the streamed bytes plus all but one block
# of it); since PR 47 the grouped-query cells' backward is that kernel too
# (what its call asks for in all is at most `ONE_KERNEL_VMEM`), their
# forward as it was: four heads a step; PR 48's looped decoder (16 equal
# heads of 128) beside them: two heads a step, 8 MiB of dq^T
CELL_PLANS = {
    "bertlarge (both cells)": ((512, 512, 16, 64, 64, 16, False),
                               (512, 512, 4, True, 10_485_760, 524_288)),
    "kimilinear latent": ((8192, 8192, 32, 192, 128, 32, True),
                          (512, 512, 2, True, 23_855_104, 12_582_912)),
    "trinitymini window and global": ((8192, 8192, 32, 128, 128, 4, True),
                                      (512, 512, 4, True, 46_661_632,
                                       33_554_432)),
    "nemotron3super share": ((8192, 8192, 4, 128, 128, 1, True),
                             (512, 512, 4, True, 26_738_688, 16_777_216)),
    "mellum2 window and global": ((16384, 16384, 32, 128, 128, 4, True),
                                  (512, 512, 4, True, 80_216_064,
                                   67_108_864)),
    "glm47flash latent": ((16384, 16384, 20, 256, 256, 20, True),
                          (512, 512, 1, True, 26_738_688, 16_777_216)),
    "ouro26b full": ((8192, 8192, 16, 128, 128, 16, True),
                     (512, 512, 2, True, 18_350_080, 8_388_608)),
}


@pytest.mark.parametrize("cell", sorted(CELL_PLANS))
def test_every_cells_attention_plan_is_pinned(cell):
    """A change to the plan that helps one head width shows here for every
    cell; 20 heads of 256 (divisors up to `MAX_HEADS`: 1, 2, 4, 5) take
    one head a step at `MAX_BLOCK`, the only slab whose dk/dv kernel fits
    `VMEM_BUDGET` at blocks of 512.  What a kernel streams stays under the
    budget, but for the group of eight in ONE backward kernel (15.2 MB,
    under a limit the call sets); the dq^T that kernel keeps past one
    block is beside it."""
    from geomx_tpu.ops.flash_attention import (VMEM_BUDGET, AttentionPlan,
                                               attention_plan)
    (q_len, kv_len, heads, d, dv, kv_heads, causal), want = CELL_PLANS[cell]
    plan = attention_plan(q_len, kv_len, heads, d, dv, jnp.bfloat16, causal,
                          kv_heads=kv_heads)
    assert plan == AttentionPlan(*want)
    kept = plan.resident_bytes - plan.resident_bytes // (q_len // 512)
    assert (plan.vmem_bytes - kept <= VMEM_BUDGET) == (
        heads // kv_heads != 8)
