"""Ask the TPU's compiler, without a TPU: the compression engine's kernels.

Every Pallas kernel of the main path is compiled by the installed
libtpu for a *described* ``v5e:2x2`` device (the `chip` fixture of
``conftest.py``; no chip attached) at the flagship's size (ResNet-20: 65
leaves, 272,474 parameters, k = 2,725) and at one large size (4M elements
/ L = 8,192); the decompress also at the chip benchmark's own bucket
sizes.  This is the guard the Mosaic-lowering tests (``jax.export`` +
``"tpu_custom_call" in mlir_module()``) cannot give: a kernel that lowers
can still be refused by the chip's compiler for an unaligned slice or for
VMEM it does not have — which is how ``bsc_select_pack``,
``fused_flatten`` and ``fused_unflatten`` passed every interpret-mode test
and could not run on a chip.  The attention kernels are in
``test_tpu_compile_attention.py``, the decoders' in
``test_tpu_compile_decoders.py``: three files, so that three workers share
the compiles.

Nothing executes here, so results are checked elsewhere (interpret-mode
parity tests; ``chip_smoke.py`` on the chip).  Skipped only where the
topology cannot be described.
"""

import jax
import jax.numpy as jnp
import pytest

import tpu_compile_checks as checks
from tpu_compile_checks import f32, i32

RESNET20_PARAMS = 272_474
RESNET20_BUCKET = 272_512        # lane-padded
RESNET20_K = 2_726               # ceil(1% of the bucket)
BIG = 4_000_000


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
    "merge_tree-2x82": lambda: _merge(164, 1),
    "merge_tree-2x2726": lambda: _merge(2 * RESNET20_K + 2, 1),
    "merge_tree-4x20000": lambda: _merge(80_002, 2),
    "merge_tree-4M": lambda: _merge(BIG, 3),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_v5e_compiler_accepts(chip, case):
    assert "tpu_custom_call" in checks.compiled_text(chip, *CASES[case]())


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
    for n, want in ((4_194_304, {"bsc_select_pack_count",
                                 "bsc_select_pack_place"}),
                    (7_040, {"bsc_select_pack"})):
        calls = checks.kernel_calls(checks.compiled_text(
            chip, *_select(n, -(-n // 100))))
        assert {c.split(".")[0] for c in calls} == want, calls


def test_the_probe_kernel_carries_a_name_no_metric_divides_by(chip):
    """`select_pack_roofline_pct` divides by the time of every kernel
    whose name starts with `bsc_select_pack`, `compress_kernels_ms` sums
    its list of prefixes: the probe's kernel is in neither, and
    `boundary_ms` finds it by its scope."""
    from benchmark.layer_metrics import compress_kernels_ms
    text = checks.compiled_text(chip, *_probe(4_194_304))
    calls = checks.kernel_calls(text)
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
    calls = checks.kernel_calls(text)
    assert {"bsc_boundary_probe", "bsc_select_pack", "bsc_select_pack_count",
            "bsc_select_pack_place", "bsc_scatter_add", "fused_flatten",
            "fused_unflatten"} == {c.split(".")[0] for c in calls}, calls
    for scope in ("compress/boundary", "bsc/select_pack", "bsc/scatter_add",
                  "compress/merge", "compress/flatten", "compress/unflatten",
                  "dc_allreduce/bucket0"):
        assert scope + "/" in text, scope
    assert not re.search(r"\b(approx-)?top-?k\b|TopK", text)
