"""Ask the TPU's compiler, without a TPU.

Every Pallas kernel of the main path is compiled by the installed
libtpu for a *described* ``v5e:2x2`` device (no chip attached) at the
flagship's size (ResNet-20: 65 leaves, 272,474 parameters, k = 2,725)
and at one large size (4M elements / L = 8,192); the decompress also at
the chip benchmark's own bucket sizes.  This is the guard the
Mosaic-lowering tests (``jax.export`` + ``"tpu_custom_call" in
mlir_module()``) cannot give: a kernel that lowers can still be refused
by the chip's compiler for an unaligned slice or for VMEM it does not
have — which is how ``bsc_select_pack``, ``fused_flatten`` and
``fused_unflatten`` passed every interpret-mode test and could not run
on a chip.

Nothing executes here, so results are checked elsewhere (interpret-mode
parity tests; ``chip_smoke.py`` on the chip).  Skipped only where the
topology cannot be described.
"""

import functools
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # keep libtpu logs out of /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

RESNET20_PARAMS = 272_474
RESNET20_BUCKET = 272_512        # lane-padded
RESNET20_K = 2_726               # ceil(1% of the bucket)
BIG = 4_000_000


@pytest.fixture(scope="module")
def chip():
    """One described v5e device, with the persistent compile cache off:
    an executable compiled for an unattached chip is written to the
    cache but cannot be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu in this installation
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e!r}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _resnet20_leaves():
    from geomx_tpu.models import ResNet20
    shapes = jax.eval_shape(
        lambda: ResNet20(num_classes=10).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)), train=False))
    leaves = [jax.ShapeDtypeStruct((leaf.size,), jnp.float32)
              for leaf in jax.tree.leaves(shapes["params"])]
    assert sum(leaf.shape[0] for leaf in leaves) == RESNET20_PARAMS
    return leaves


def _big_leaves():
    # one 16 MiB bucket of odd-sized leaves: every alignment case at once
    return [jax.ShapeDtypeStruct((n,), jnp.float32)
            for n in (1_000_003, 2048 * 512, 999, 1_500_000, 450_001)]


def f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def _bucket_case(direction, leaves_fn, bucket_bytes):
    from geomx_tpu.compression.bucketing import GradientBucketer
    from geomx_tpu.ops import fused_flatten, fused_unflatten
    leaves = leaves_fn()
    bk = GradientBucketer(leaves, bucket_bytes)
    assert bk.num_buckets == 1
    layout, sizes = bk._layout(), tuple(bk.bucket_sizes)
    if direction == "flatten":
        return (lambda *ls: fused_flatten(ls, layout, sizes)), leaves
    return ((lambda *bs: fused_unflatten(bs, layout, tuple(bk.leaf_sizes))),
            [f32(n) for n in sizes])


def _twobit(n):
    from geomx_tpu.ops import quantize_2bit
    return (lambda g, r: quantize_2bit(g, r, threshold=0.5)), [f32(n), f32(n)]


def _twobit_inv(n):
    from geomx_tpu.ops import dequantize_2bit
    words = -(-n // 2048) * 128
    return (lambda p: dequantize_2bit(p, n=n, threshold=0.5)), [i32(words)]


def _select(n, k):
    from geomx_tpu.ops import bsc_select_pack
    return ((lambda g, u, v, t: bsc_select_pack(g, u, v, t, k=k)),
            [f32(n), f32(n), f32(n), f32()])


def _probe(n):
    from geomx_tpu.ops.bsc_pallas import bsc_sampled_boundary
    return ((lambda g, u, v: bsc_sampled_boundary(g, u, v, -(-n // 100))),
            [f32(n), f32(n), f32(n)])


def _scatter(n, pairs):
    from geomx_tpu.ops import bsc_scatter_add
    return (lambda v, i: bsc_scatter_add(v, i, n=n)), [f32(pairs), i32(pairs)]


def _merge(pairs, rounds):
    from geomx_tpu.ops.merge_pallas import _merge_tree_pallas
    return ((lambda v, k, r: _merge_tree_pallas(v, k, r, rounds=rounds)),
            [f32(pairs), i32(pairs), i32(pairs)])


def _sgd(n):
    from geomx_tpu.ops import fused_sgd_momentum
    return ((lambda p, g, m: fused_sgd_momentum(p, g, m, lr=0.1,
                                                momentum=0.9)),
            [f32(n)] * 3)


def _adam(n):
    from geomx_tpu.ops import fused_adam
    return ((lambda p, g, m, v, a, b: fused_adam(
        p, g, m, v, a, b, lr=1e-3, b1=0.9, b2=0.999, eps=1e-8)),
        [f32(n)] * 4 + [f32(), f32()])


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
    causal: 136 block pairs of 512, dq and dk/dv kernels); one sequence of
    the second decoder's grouped-query attention (8,192 x 32 query heads
    on 4 key/value heads of 128), in a window layer (a band of 2,048 keys:
    70 pairs) and in a global one; one sequence of the third decoder's
    share of its attention layer (four query heads on the one key/value
    head they read); one sequence of the fourth decoder's (16,384 x 32 on
    4 of 128) in a window layer (a band of 1,024 keys, two blocks wide)
    and in a full one (528 block pairs)."""
    from geomx_tpu.ops import flash_attention_bwd, flash_attention_with_lse
    b, L, h, kv, d, dv, causal, window = {
        "bert": (16, 512, 16, 16, 64, 64, False, None),
        "latent": (1, 8192, 32, 32, 192, 128, True, None),
        "window": (1, 8192, 32, 4, 128, 128, True, 2048),
        "global": (1, 8192, 32, 4, 128, 128, True, None),
        "share": (1, 8192, 4, 1, 128, 128, True, None),
        "window-16k": (1, 16384, 32, 4, 128, 128, True, 1024),
        "global-16k": (1, 16384, 32, 4, 128, 128, True, None)}[which]
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
    "quantize_2bit-resnet20": lambda: _twobit(RESNET20_BUCKET),
    "quantize_2bit-4M": lambda: _twobit(BIG),
    "dequantize_2bit-resnet20": lambda: _twobit_inv(RESNET20_BUCKET),
    "dequantize_2bit-4M": lambda: _twobit_inv(BIG),
    "bsc_boundary_probe-resnet20": lambda: _probe(RESNET20_BUCKET),
    "bsc_boundary_probe-1Mi": lambda: _probe(1_048_576),
    "bsc_boundary_probe-bertlarge-ffn": lambda: _probe(4_194_304),
    "bsc_boundary_probe-one-tile": lambda: _probe(8_320),
    "bsc_select_pack-resnet20": lambda: _select(RESNET20_BUCKET, RESNET20_K),
    "bsc_select_pack-4M": lambda: _select(BIG, BIG // 100),
    # the benchmark's own buckets, and one the resident output slabs of
    # before PR 28 could not take (k above 1 << 19): nothing executes
    "bsc_select_pack-bertlarge-embedding": lambda: _select(31_254_528,
                                                           312_546),
    "bsc_select_pack-bertlarge-ffn": lambda: _select(4_194_304, 41_944),
    "bsc_select_pack-one-tile": lambda: _select(7_040, 71),
    "bsc_select_pack-64Mi": lambda: _select(1 << 26, 671_089),
    "bsc_scatter_add-resnet20": lambda: _scatter(RESNET20_BUCKET,
                                                 2 * RESNET20_K),
    "bsc_scatter_add-4M": lambda: _scatter(BIG, 4 * (BIG // 100)),
    # the benchmark's own buckets (a leaf larger than a bucket's capacity
    # has a bucket of its own): n, k = ceil(n / 100)
    "bsc_scatter_add-bertlarge-embedding": lambda: _scatter(31_254_528,
                                                            312_546),
    "bsc_scatter_add-bertlarge-ffn": lambda: _scatter(4_194_304, 41_944),
    "bsc_scatter_add-bertlarge-ffn-2-parties": lambda: _scatter(
        4_194_304, 2 * 41_944),
    "bsc_scatter_add-resnet18-layer4": lambda: _scatter(2_359_296, 23_593),
    "fused_flatten-resnet20": lambda: _bucket_case(
        "flatten", _resnet20_leaves, 4 << 20),
    "fused_flatten-4M": lambda: _bucket_case("flatten", _big_leaves, 16 << 20),
    "fused_unflatten-resnet20": lambda: _bucket_case(
        "unflatten", _resnet20_leaves, 4 << 20),
    "fused_unflatten-4M": lambda: _bucket_case(
        "unflatten", _big_leaves, 16 << 20),
    "fused_sgd_momentum-resnet20": lambda: _sgd(RESNET20_BUCKET),
    "fused_sgd_momentum-4M": lambda: _sgd(BIG),
    "fused_adam-resnet20": lambda: _adam(RESNET20_BUCKET),
    "fused_adam-4M": lambda: _adam(BIG),
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
    "flash_attention_bwd-f32-grouped-64-wide": lambda: _grouped_narrow(),
    "fused_ring_hop-L1024": lambda: _ring_hop(1024),
    "fused_ring_hop-L2048": lambda: _ring_hop(2048),   # 8,192 over 4 chips
    "merge_tree-2x82": lambda: _merge(164, 1),
    "merge_tree-2x2726": lambda: _merge(2 * RESNET20_K + 2, 1),
    "merge_tree-4x20000": lambda: _merge(80_002, 2),
    "merge_tree-4M": lambda: _merge(BIG, 3),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_v5e_compiler_accepts(chip, case):
    fn, shapes = CASES[case]()
    args = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip)
            for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_v5e_compiler_accepts_the_kda_scan(chip, direction):
    """The chunked delta-rule scan (plain XLA: a `while` over the chunks
    and the batched products around it) at the chip benchmark's widths,
    one sequence of 8,192 tokens and 8 of the 32 heads of 128, bf16
    operands: what the compiler makes of the sub-block layout, and that it
    fits."""
    from geomx_tpu.ops.kda import kda_chunked
    wide = lambda dtype: jax.ShapeDtypeStruct((1, 8, 8192, 128), dtype,
                                              sharding=chip)
    args = [wide(jnp.float32), wide(jnp.float32), wide(jnp.bfloat16),
            wide(jnp.float32),
            jax.ShapeDtypeStruct((1, 8, 8192), jnp.float32, sharding=chip)]
    run = functools.partial(kda_chunked, dtype=jnp.bfloat16)
    if direction == "backward":
        fn = jax.grad(lambda *a: jnp.sum(run(*a)), argnums=(0, 1, 2, 3, 4))
    else:
        fn = run
    compiled = jax.jit(fn).lower(*args).compile()
    assert " while(" in compiled.as_text()


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_v5e_compiler_accepts_the_kda_kernels(chip, direction):
    """The scan's Pallas kernels (`ops/kda_pallas.py`) lowered native at
    the chip benchmark's shape, one sequence of 8,192 tokens and all 32
    heads of 128, bf16 operands, through the door `KDAMixer` calls: the
    tiling of a chunk's slices, the sublane rolls of the decay pass, the
    float32 products at `HIGHEST`, and the VMEM the plan asks of the
    compiler (`vmem_limit_bytes`), forward and backward."""
    from geomx_tpu.ops import dispatch, kda_pallas
    wide = lambda dtype: jax.ShapeDtypeStruct((1, 32, 8192, 128), dtype,
                                              sharding=chip)
    args = [wide(jnp.float32), wide(jnp.float32), wide(jnp.bfloat16),
            wide(jnp.float32),
            jax.ShapeDtypeStruct((1, 32, 8192), jnp.float32, sharding=chip)]
    run = lambda *a: dispatch.kda(*a, chunk=64, sub=16, dtype=jnp.bfloat16)
    if direction == "backward":
        fn = jax.grad(lambda *a: jnp.sum(run(*a)), argnums=(0, 1, 2, 3, 4))
    else:
        fn = run
    with dispatch.kernels("native"):
        text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text and " while(" not in text
    assert ("kda_scan_bwd" in text) == (direction == "backward")
    plan = kda_pallas.kda_plan(8192, 32, 128, 128, 64, jnp.bfloat16)
    assert (plan.heads, plan.chunks) == (4, 4)


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_v5e_compiler_accepts_the_ssd_scan(chip, direction):
    """The Mamba-2 scan in its chunkwise matrix form (plain XLA through
    the door `Mamba2Mixer` calls) at the chip benchmark's widths, one
    sequence of 8,192 tokens, the 16 heads of 64 and the one B/C group of
    128 a chip holds, chunk 128, bf16 operands: no `while` (the hand-over
    from chunk to chunk is one product), and it fits."""
    from geomx_tpu.ops import dispatch
    on = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                   sharding=chip)
    args = [on((1, 8192, 16, 64), jnp.bfloat16),
            on((1, 8192, 16), jnp.float32), on((16,), jnp.float32),
            on((1, 8192, 1, 128), jnp.bfloat16),
            on((1, 8192, 1, 128), jnp.bfloat16)]
    run = lambda *a: dispatch.ssd(*a, 128, jnp.bfloat16)
    if direction == "backward":
        fn = jax.grad(lambda *a: jnp.sum(run(*a)), argnums=(0, 1, 2, 3, 4))
    else:
        fn = run
    with dispatch.kernels("native"):
        compiled = jax.jit(fn).lower(*args).compile()
    assert " while(" not in compiled.as_text()
    # a sequence's decay matrices and their cotangents, not gigabytes
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_v5e_compiler_accepts_the_ungated_held_experts(chip, direction):
    """The third decoder cell's routed experts: 16,384 tokens of the
    1,024-wide latent, 22 of 512 experts a token, 8 held of width 2,688,
    un-gated squared ReLU (no gate kernel), tiles of 512 in a first pool
    of 11,264 places (twice what even routing sends the chip): the
    grouped products' tiles at K = 1,024 / N = 2,688 fit VMEM both ways,
    and a row of the latent is whole tiles, so the pools' rows go back
    through `moe_row_scatter_add`."""
    import re
    from geomx_tpu.ops import dispatch
    from geomx_tpu.ops.held_experts import held_experts
    tokens, d, held, f, rows, pool, k = 16384, 1024, 8, 2688, 512, 11264, 22
    on = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                   sharding=chip)
    args = [on((tokens, d), jnp.bfloat16), on((tokens, k), jnp.int32),
            on((tokens, k), jnp.float32), on((held, d, f), jnp.float32),
            on((held, f, d), jnp.float32)]
    run = lambda x, idx, w, up, down: held_experts(
        x, idx, w, None, up, down, 0, rows, False, pool)
    if direction == "backward":
        fn = jax.grad(lambda *a: jnp.sum(run(*a)[0]), argnums=(0, 2, 3, 4))
    else:
        fn = run
    with dispatch.kernels("native"):
        text = jax.jit(fn).lower(*args).compile().as_text()
    calls = _kernel_calls(text)
    assert any(c.startswith("gmm") for c in calls), calls
    assert any(c.startswith("tgmm") for c in calls) == (
        direction == "backward"), calls
    moves = [c.split(".")[0] for c in calls if c.startswith("moe_row")]
    assert moves == ["moe_row_scatter_add"] * 2, calls
    assert not re.search(r"= f32\[\d+,%d\]\S* scatter\(" % d, text)


# (tokens, hidden, held, width, tile, first pool): the decoder cells'
# SwiGLU expert layers, 8 picks a token (131,072 assignments)
HELD_EXPERTS = {
    "kimi-8-of-256": (16384, 2304, 8, 1024, 512, None),
    "trinity-16-of-128": (16384, 2048, 16, 1024, 512, 32768),
    "mellum-16-of-64": (16384, 2304, 16, 896, 512, 65536),
}


def _kernel_calls(text):
    """The compiled program's Pallas calls by instruction name, in order."""
    import re
    return re.findall(r"%([\w.-]+) = [^\n]*custom_call_target="
                      r'"tpu_custom_call"', text)


@pytest.mark.parametrize("direction", ["forward", "backward"])
@pytest.mark.parametrize("cell", sorted(HELD_EXPERTS))
def test_v5e_compiler_accepts_the_held_experts(chip, cell, direction):
    """The held experts' walk at the chip benchmark's sizes (16,384 tokens,
    top 8, bf16 operands: 8 held experts of 1,024 at hidden 2,304 with the
    default first pool of 8,192 places; 16 held at hidden 2,048 with a
    first pool of 32,768): the grouped-product kernels' tiles have to fit
    VMEM, forward and backward; at 2,048 the pools' rows go back through
    the kernel `moe_row_scatter_add` (ops/moe_rows_pallas.py: y forward, dx
    backward), which `moe_dispatch_ms` finds by its scope, and no XLA
    scatter of rows is left (the gathers are XLA's: they cost what their
    bytes cost); at 2,304 the door keeps XLA's scatter-add."""
    import re
    from geomx_tpu.ops import dispatch
    from geomx_tpu.ops.held_experts import held_experts
    tokens, d, held, f, rows, pool = HELD_EXPERTS[cell]
    on = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                   sharding=chip)
    args = [on((tokens, d), jnp.bfloat16), on((tokens, 8), jnp.int32),
            on((tokens, 8), jnp.float32), on((held, d, f), jnp.float32),
            on((held, d, f), jnp.float32), on((held, f, d), jnp.float32)]
    run = lambda x, idx, w, *mats: held_experts(x, idx, w, *mats, 0, rows,
                                                False, pool)
    if direction == "backward":
        fn = jax.grad(lambda x, idx, w, *mats: jnp.sum(
            run(x, idx, w, *mats)[0]), argnums=(0, 2, 3, 4, 5))
    else:
        fn = run
    with dispatch.kernels("native"):
        text = jax.jit(fn).lower(*args).compile().as_text()
    calls = _kernel_calls(text)
    moves = [c.split(".")[0] for c in calls if c.startswith("moe_row")]
    assert any(c.startswith("gmm") for c in calls), calls
    xla_scatters = re.search(r"= f32\[\d+,%d\]\S* scatter\(" % d, text)
    if d % 1024 == 0:
        # the first pool and the `while`'s later pools, each once
        assert moves == ["moe_row_scatter_add"] * 2, calls
        assert not xla_scatters
    else:
        # slabs of 2,304 would pad: the door keeps XLA's scatter-add
        assert moves == [] and xla_scatters, calls


@pytest.mark.parametrize("cell", sorted(HELD_EXPERTS))
def test_the_row_kernel_carries_the_name_the_docs_give(chip, cell):
    """The scatter-add kernel alone at a cell's first pool: one custom
    call, by its name, at the tile its VMEM budget gives."""
    from geomx_tpu.ops import moe_rows_pallas as rows_ops
    tokens, d, held, _, rows, pool = HELD_EXPERTS[cell]
    places = pool or 2 * held * rows
    on = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    text = jax.jit(rows_ops.moe_row_scatter_add).lower(
        on((tokens, d), jnp.float32), on((places, d), jnp.float32),
        on((places,), jnp.int32), on((held,), jnp.int32)).compile().as_text()
    calls = _kernel_calls(text)
    assert [c.split(".")[0] for c in calls] == ["moe_row_scatter_add"], calls
    assert rows_ops.tile_rows(places, d) == (512 if d == 2048 else 256)


@pytest.mark.parametrize("kind", ["window", "global"])
def test_v5e_compiler_accepts_the_gqa_mixers_streamed_pass(chip, kind):
    """`models/afmoe.GQAMixer` at the Trinity cell's shape (one sequence of
    8,192 tokens, hidden 2,048, bf16, 32 query heads on 4 of 128; a window
    of 2,048 with rotary, or global without), forward and gradient in one
    program.  A window layer's q/k norm + rotary is the kernel pair of
    `ops/gqa_elementwise.py`, each once and by the names
    `tools/scope_ops.py --scope gqa/proj` lists them under, and its plans
    fit their VMEM budget; a global layer's norm alone is XLA's (PERF.md,
    PR 37).  Neither program holds a concatenate as large as q or k
    (rotary's halves)."""
    import math
    import re
    from geomx_tpu.models.afmoe import GQAMixer
    from geomx_tpu.ops import dispatch
    from geomx_tpu.ops import gqa_elementwise as ge
    length, hidden, heads, kv_heads, d = 8192, 2048, 32, 4, 128
    mixer = GQAMixer(heads, kv_heads, d, 2048 if kind == "window" else None,
                     10000.0 if kind == "window" else None, 1e-5,
                     jnp.bfloat16)
    on = lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                           sharding=chip)
    x = jax.ShapeDtypeStruct((1, length, hidden), jnp.bfloat16)
    params = jax.tree.map(on, jax.eval_shape(
        mixer.init, jax.random.PRNGKey(0), x)["params"])
    loss = lambda p, x: jnp.sum(
        mixer.apply({"params": p}, x).astype(jnp.float32))
    with dispatch.kernels("native"):
        text = jax.jit(jax.value_and_grad(loss, (0, 1))).lower(
            params, on(x)).compile().as_text()
    calls = [c.split(".")[0] for c in _kernel_calls(text)]
    for name in ("gqa_norm_rotary_fwd", "gqa_norm_rotary_bwd"):
        assert calls.count(name) == (kind == "window"), calls
    wide = {length * heads * d, length * kv_heads * d}
    for shape in re.findall(r"= \w+\[([\d,]+)\]\S* concatenate\(", text):
        assert math.prod(int(n) for n in shape.split(",")) not in wide, shape
    q, k = (1, length, heads, d), (1, length, kv_heads, d)
    for backward in (False, True):
        plan = ge.norm_rotary_plan(q, k, jnp.bfloat16, backward)
        assert plan.tile >= 128 and plan.vmem_bytes <= ge.VMEM_BUDGET


@pytest.mark.parametrize("kind", ["window", "global"])
def test_v5e_compiler_accepts_the_mixer_under_either_table(chip, kind):
    """`models/afmoe.GQAMixer` as `models/mellum.py` builds it, at the
    fourth decoder cell's shape (one sequence of 16,384 tokens, hidden
    2,304, bf16, 32 query heads on 4 of 128, no gate): a window layer (a
    band of 1,024, plain rotary at theta 500,000) and a full one (YaRN's
    table at the published numbers), forward and gradient in one program.
    Either kind's q/k norm + rotary is the kernel pair of
    `ops/gqa_elementwise.py`, each once: the tables are operands, so
    YaRN's is no other kernel."""
    from geomx_tpu.models.mellum import MellumConfig
    from geomx_tpu.ops import dispatch
    from geomx_tpu.ops import gqa_elementwise as ge
    length, hidden = 16384, 2304
    cfg = MellumConfig(
        vocab=24576, hidden=hidden, layers=(), num_heads=32, num_kv_heads=4,
        head_dim=128, window=1024, rope_theta=500000.0, expert_width=896,
        num_experts=64, experts_held=16, expert_offset=0, top_k=8,
        yarn=ge.Yarn(500000.0, 16.0, 8192, 32.0, 1.0, 1.2772588722239782))
    mixer = cfg.make_mixer(kind, jnp.bfloat16)
    assert isinstance(mixer.rope, ge.Yarn) == (kind == "global")
    on = lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                           sharding=chip)
    x = jax.ShapeDtypeStruct((1, length, hidden), jnp.bfloat16)
    params = jax.tree.map(on, jax.eval_shape(
        mixer.init, jax.random.PRNGKey(0), x)["params"])
    assert "gate_kernel" not in params
    loss = lambda p, x: jnp.sum(
        mixer.apply({"params": p}, x).astype(jnp.float32))
    with dispatch.kernels("native"):
        text = jax.jit(jax.value_and_grad(loss, (0, 1))).lower(
            params, on(x)).compile().as_text()
    calls = [c.split(".")[0] for c in _kernel_calls(text)]
    for name in ("gqa_norm_rotary_fwd", "gqa_norm_rotary_bwd",
                 "flash_attention_fwd", "flash_attention_bwd_dq",
                 "flash_attention_bwd_dkv"):
        assert calls.count(name) == 1, calls
    for backward in (False, True):
        plan = ge.norm_rotary_plan((1, length, 32, 128), (1, length, 4, 128),
                                   jnp.bfloat16, backward)
        assert plan.tile >= 128 and plan.vmem_bytes <= ge.VMEM_BUDGET


def test_fused_bucket_kernels_refuse_what_vmem_cannot_hold():
    """Above the size the whole-array VMEM refs support the kernels
    raise — they never switch paths quietly."""
    from geomx_tpu.ops import fused_flatten
    from geomx_tpu.ops.bucket_pallas import MAX_FUSED_BUCKET_ELEMS
    n = MAX_FUSED_BUCKET_ELEMS
    layout = ((0, 0, n), (0, n, 7))
    with pytest.raises(ValueError, match="GEOMX_BUCKET_BYTES"):
        jax.eval_shape(
            lambda a, b: fused_flatten((a, b), layout, (n + 128,)),
            f32(n), f32(7))


def test_select_pack_kernels_carry_the_name_the_benchmark_reads(chip):
    """`select_pack_roofline_pct` and `compress_kernels_ms` find the
    select/pack's kernels by the prefix `bsc_select_pack` of their
    instruction names (benchmark/trace_reduce.family_time_s): both passes
    of a bucket of several tiles, and the one call of a bucket of one."""
    import re
    for n, want in ((4_194_304, {"bsc_select_pack_count",
                                 "bsc_select_pack_place"}),
                    (7_040, {"bsc_select_pack"})):
        fn, shapes = _select(n, -(-n // 100))
        args = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip)
                for s in shapes]
        text = jax.jit(fn).lower(*args).compile().as_text()
        calls = re.findall(r"%([\w.-]+) = [^\n]*custom_call_target="
                           r'"tpu_custom_call"', text)
        assert {c.split(".")[0] for c in calls} == want, calls


def test_the_probe_kernel_carries_a_name_no_metric_divides_by(chip):
    """`select_pack_roofline_pct` divides by the time of every kernel
    whose name starts with `bsc_select_pack`, `compress_kernels_ms` sums
    its list of prefixes: the probe's kernel is in neither, and
    `boundary_ms` finds it by its scope."""
    import re
    from benchmark.layer_metrics import compress_kernels_ms
    fn, shapes = _probe(4_194_304)
    args = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip)
            for s in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    calls = re.findall(r"%([\w.-]+) = [^\n]*custom_call_target="
                       r'"tpu_custom_call"', text)
    assert {c.split(".")[0] for c in calls} == {"bsc_boundary_probe"}, calls
    assert not "bsc_boundary_probe".startswith(compress_kernels_ms.PREFIXES)
    assert "gather" not in text


def test_the_probe_adds_no_program_to_a_step(chip, monkeypatch):
    """Loaded program code counts against `peak_hbm_gib` (PERF.md, PRs 25
    and 28), and a step holds a probe for every bucket.  One bucketed
    Bi-Sparse allreduce over the three sizes above, the door's kernels
    against the same program with the gathers (the door's choice undone
    by hand): the generated code may not grow by more than 64 KiB a
    bucket.  Measured here: it shrinks (0.62 MB a probe against the
    gathers' 0.71)."""
    from geomx_tpu.compression.bisparse import BiSparseCompressor
    from geomx_tpu.compression.bucketing import BucketedCompressor
    from geomx_tpu.ops import bsc_pallas, dispatch
    from geomx_tpu.ops.dispatch import kernels

    sizes = [(RESNET20_BUCKET,), (1_048_576,), (4_194_304,)]

    def code_bytes():
        comp = BucketedCompressor(BiSparseCompressor(0.01))
        grads = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=chip)
                 for s in sizes]
        state = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
            jax.eval_shape(comp.init_state, grads))
        with kernels("native"):
            lowered = jax.jit(
                lambda g, s: comp.allreduce(g, s, "dc", 1)).lower(grads, state)
        compiled = lowered.compile()
        return (compiled.memory_analysis().generated_code_size_in_bytes,
                compiled.as_text().count("bsc_boundary_probe"))

    door, probes = code_bytes()
    assert probes >= len(sizes)
    monkeypatch.setattr(dispatch, "sampled_boundary",
                        bsc_pallas.sampled_boundary_guv)
    gathers, probes = code_bytes()
    assert probes == 0
    assert door <= gathers + len(sizes) * 64 * 1024, (door, gathers)


@pytest.mark.parametrize("which,want", [
    ("bert", {"flash_attention_fwd", "flash_attention_bwd"}),
    ("latent", {"flash_attention_fwd", "flash_attention_bwd_dq",
                "flash_attention_bwd_dkv"}),
])
def test_attention_kernels_carry_the_name_the_benchmark_reads(chip, which,
                                                              want):
    """`flash_attn_roofline_pct` finds the kernels by the prefix
    `flash_attention` of their instruction names
    (benchmark/layer_metrics/flash_attn_roofline_pct.PREFIXES): a kernel
    under another name would leave the share's divisor short."""
    import re
    calls = []
    for direction in ("forward", "backward"):
        fn, shapes = _cell_attention(which, direction)
        args = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip)
                for s in shapes]
        text = jax.jit(fn).lower(*args).compile().as_text()
        calls += re.findall(r"%([\w.-]+) = [^\n]*custom_call_target="
                            r'"tpu_custom_call"', text)
    assert {c.split(".")[0] for c in calls} == want, calls
    assert all(c.startswith("flash_attention") for c in calls)


def test_the_bucket_allreduce_gets_the_kernels_through_the_door(chip):
    """What a cell compiles: "bsc,0.01" -> get_compressor -> the bucketed
    dc-tier allreduce, traced under the `native` hook (what
    ops/dispatch.py answers on a TPU).  Buckets of one tile and of
    several: the custom calls carry the names the benchmark reads, and
    no top-k of any kind is left in the program."""
    import re
    from geomx_tpu.compression import get_compressor
    from geomx_tpu.compression.bucketing import maybe_bucketed
    from geomx_tpu.ops.dispatch import kernels

    comp = maybe_bucketed(get_compressor("bsc,0.01"), bucket_bytes=64 * 1024)
    shapes = [(100, 70), (33,), (1_500_000,), (64, 64)]
    grads = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=chip)
             for s in shapes]
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
        jax.eval_shape(comp.init_state, grads))
    with kernels("native"):
        lowered = jax.jit(
            lambda g, s: comp.allreduce(g, s, "dc", 1)).lower(grads, state)
    text = lowered.compile().as_text()
    calls = re.findall(r"%([\w.-]+) = [^\n]*custom_call_target="
                       r'"tpu_custom_call"', text)
    assert {"bsc_boundary_probe", "bsc_select_pack", "bsc_select_pack_count",
            "bsc_select_pack_place", "bsc_scatter_add", "fused_flatten",
            "fused_unflatten"} == {c.split(".")[0] for c in calls}, calls
    for scope in ("compress/boundary", "bsc/select_pack", "bsc/scatter_add",
                  "compress/merge", "compress/flatten", "compress/unflatten",
                  "dc_allreduce/bucket0"):
        assert scope + "/" in text, scope
    assert not re.search(r"\b(approx-)?top-?k\b|TopK", text)
