"""Fused BSC / bucket kernel suite (docs/kernels.md).

Three layers of evidence, all on CPU:

- *Parity*: the Pallas kernels in interpret mode are bit-identical to
  the jnp reference paths — values, indices (sentinels, tie order),
  error-feedback residuals — across odd sizes, all-sentinel, and
  overflow-past-k inputs.  Both sides run under jit so XLA applies the
  same FMA contraction to the momentum arithmetic.
- *Lowering*: every kernel cross-lowers to TPU Mosaic on a CPU host
  (same guard as the flash/2-bit kernels), so tiling/packing breakage
  surfaces in CI, not on chip.
- *Structure*: the lowered-HLO op counts show the unfused chain's dense
  intermediates (scatter, cumsum expansion, per-leaf copies) are GONE
  from the fused path.
- *The door*: the engine gets its kernels through ops/dispatch.py, from
  the platform alone: no environment name, spec key or argument selects.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from geomx_tpu.compression import BiSparseCompressor
from geomx_tpu.compression.bucketing import GradientBucketer
from geomx_tpu.ops.bsc_pallas import (bsc_boundary_probe,
                                      bsc_sampled_boundary, bsc_scatter_add,
                                      bsc_select_pack, probe_plan,
                                      sampled_boundary_guv)
from geomx_tpu.ops.dispatch import kernels


class _Under:
    """``obj`` with every method traced under ``kernels(mode)``: what a
    TPU ("native") or a parity test ("interpret") gets through
    ops/dispatch.py where the CPU default gets the jnp forms."""

    def __init__(self, obj, mode):
        self._obj, self._mode = obj, mode

    def __getattr__(self, name):
        attr = getattr(self._obj, name)
        if not callable(attr):
            return attr

        def call(*args, **kwargs):
            with kernels(self._mode):
                return attr(*args, **kwargs)
        return call


def _pair(ratio=0.01, **kw):
    """(jnp-reference, fused-interpret) views of one compressor."""
    c = BiSparseCompressor(ratio=ratio, min_sparse_size=1, **kw)
    return c, _Under(c, "interpret")


def _compress_pair(cj, cf, g, u, v):
    jj = jax.jit(lambda a, b, c: cj.compress(a, b, c))
    jf = jax.jit(lambda a, b, c: cf.compress(a, b, c))
    return jj(g, u, v), jf(g, u, v)


def _assert_bitwise(ref, fus):
    for name, a, b in zip(("vals", "idx", "new_u", "new_v"), ref, fus):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


# ---------- select/pack parity (interpret mode) ----------

@pytest.mark.parametrize("n,ratio", [
    (5000, 0.01),     # odd size: padding rows + partial final block
    (1024, 0.05),     # exactly one (8, 128) tile
    (1023, 0.03),     # one element short of it
    (131072, 0.01),   # four whole tiles, one output block
    (10, 0.5),        # tiny: n < lane width
    (32768, 0.02),    # exactly one tile: the path with no schedule
    (32769, 0.02),    # one element more: two tiles, the second all padding
    (100000, 0.3),    # n no multiple of the tile nor of a lane; 30,000
                      # pairs over four output blocks, tiles that span two
    (70000, 0.9),     # nearly everything emitted: the densest placement
])
def test_select_pack_parity_random(rng, n, ratio):
    cj, cf = _pair(ratio=ratio)
    g = jnp.asarray(rng.normal(0, 1, n).astype(np.float32))
    u = jnp.asarray(rng.normal(0, 0.1, n).astype(np.float32))
    v = jnp.asarray(rng.normal(0, 0.2, n).astype(np.float32))
    ref, fus = _compress_pair(cj, cf, g, u, v)
    _assert_bitwise(ref, fus)


def test_select_pack_parity_all_sentinel():
    """A sparse gradient under a high sampled boundary emits fewer than
    k pairs: the fused path must reproduce the exact sentinel tail (idx
    -1, vals 0) and leave unsent mass in the residuals."""
    n = 8192
    g = np.zeros(n, np.float32)
    g[7] = 3.0
    g[4096] = -2.0
    cj, cf = _pair()
    ref, fus = _compress_pair(cj, cf, jnp.asarray(g),
                              jnp.zeros((n,)), jnp.zeros((n,)))
    _assert_bitwise(ref, fus)
    vals, idx = np.asarray(fus[0]), np.asarray(fus[1])
    assert (idx >= 0).sum() >= 2 and vals[idx >= 0].sum() != 0
    # mass conservation: emitted + residual == momentum-corrected grad
    out = np.zeros(n, np.float32)
    out[idx[idx >= 0]] += vals[idx >= 0]
    np.testing.assert_allclose(out + np.asarray(fus[3]), g, atol=1e-6)


def test_select_pack_parity_overflow_past_k():
    """Every element tied at the boundary (constant tensor): more
    candidates than slots — the first k in index order win, exactly as
    the reference scan fills its fixed buffer."""
    n, ratio = 4096, 0.01
    cj, cf = _pair(ratio=ratio)
    g = jnp.full((n,), -0.75, jnp.float32)
    ref, fus = _compress_pair(cj, cf, g, jnp.zeros((n,)), jnp.zeros((n,)))
    _assert_bitwise(ref, fus)
    k = cj.k_for(n)
    idx = np.asarray(fus[1])
    assert (idx >= 0).sum() == k
    np.testing.assert_array_equal(np.sort(idx), np.arange(k))


def test_select_pack_parity_all_zero():
    """All-zero input with a zero boundary: zero-valued ties fill the
    buffer (never more), and the zero PADDING the kernel adds to reach
    block shape must not claim any slot."""
    n = 5000  # not a block multiple: real zeros and pad zeros coexist
    cj, cf = _pair()
    z = jnp.zeros((n,), jnp.float32)
    ref, fus = _compress_pair(cj, cf, z, z, z)
    _assert_bitwise(ref, fus)
    idx = np.asarray(fus[1])
    assert (idx >= 0).sum() == cj.k_for(n)
    assert idx.max() < n  # no padding coordinate ever emitted


def test_select_pack_mixed_primary_and_ties(rng):
    """Quantized magnitudes produce many exact boundary ties next to
    strictly-greater elements — the two-tier rank order (all primaries
    first, ties after) must match bit-for-bit."""
    n = 20000
    g = np.round(rng.normal(0, 2, n)).astype(np.float32) * 0.5
    cj, cf = _pair(ratio=0.02)
    ref, fus = _compress_pair(cj, cf, jnp.asarray(g),
                              jnp.zeros((n,)), jnp.zeros((n,)))
    _assert_bitwise(ref, fus)


def _direct_pair(g, u, v, thr, k):
    """(jnp chain, fused-interpret) at a boundary the caller gives."""
    from geomx_tpu.ops.bsc_pallas import select_pack_ref

    thr = jnp.float32(thr)
    return (jax.jit(select_pack_ref, static_argnums=4)(g, u, v, thr, k),
            bsc_select_pack(g, u, v, thr, k, interpret=True))


def _schedule_cases():
    """Buckets of several tiles (32,768 elements) and output blocks
    (8,192 pairs) that walk the placement's schedule through its corners,
    as ``name -> (g, thr, k)`` with u = v = 0, so v' = g."""
    rng = np.random.RandomState(28)
    tile = 32768
    cases = {}
    # k falls inside the third tile: the one tile whose keep needs ranks;
    # every later tile keeps nothing and places nothing
    g = rng.normal(0, 1, 5 * tile).astype(np.float32)
    cases["k-inside-a-tile"] = (g, 1.0, int((np.abs(g[:2 * tile]) > 1).sum())
                                + 1234)
    # ties only: everything tied at thr, the first k in index order win,
    # over two output blocks and one tile's worth of slots
    cases["ties-only"] = (np.full(3 * tile + 777, -0.75, np.float32), 0.75,
                          tile // 2 + 5)
    # thr == 0 on an all-zero bucket that ends inside a lane: the padding
    # must claim no slot, nor what the last tile reads past the end
    cases["zero-boundary-padding"] = (np.zeros(2 * tile + 4000 + 77,
                                               np.float32), 0.0,
                                      2 * tile + 4077)
    # a tile wholly zero between two dense ones, primaries and ties mixed
    # (quantized magnitudes), the ties queueing after ALL primaries
    g = np.round(rng.normal(0, 2, 3 * tile)).astype(np.float32)
    g[tile:2 * tile] = 0
    cases["zero-tile-between"] = (g, 3.0, int((np.abs(g) > 3).sum()) + 900)
    # n no multiple of the tile, pairs enough for three output blocks,
    # fewer than k emitted: a sentinel tail that spans a whole block
    g = rng.normal(0, 1, 6 * tile + 12345).astype(np.float32)
    cases["sentinel-tail"] = (g, 1.5, int((np.abs(g) > 1.5).sum()) + 9000)
    # a dense stretch inside zeros (the embedding's shape): one tile emits
    # 32,768 pairs, four output blocks from one tile's frame
    g = np.zeros(4 * tile, np.float32)
    g[tile + 100:2 * tile + 100] = rng.normal(0, 1, tile) + 5.0
    cases["one-tile-fills-blocks"] = (g, 0.5, tile + 4000)
    # below one tile, both classes, ties starting mid-row
    g = np.round(rng.normal(0, 2, 20000)).astype(np.float32)
    cases["one-tile-both-classes"] = (g, 2.0,
                                      int((np.abs(g) > 2).sum()) + 333)
    return cases


_SCHEDULE_CASES = _schedule_cases()


@pytest.mark.parametrize("case", sorted(_SCHEDULE_CASES))
def test_select_pack_schedule_against_the_jnp_chain(case):
    g, thr, k = _SCHEDULE_CASES[case]
    g = jnp.asarray(g)
    z = jnp.zeros_like(g)
    ref, fus = _direct_pair(g, z, z, thr, k)
    _assert_bitwise(ref, fus)
    assert (np.asarray(fus[1]) >= 0).any()


def _place_visits(p_cnt, s_cnt, k):
    """``place_visits`` on hand-made per-tile counts, as numpy: the live
    (item, block) visits, the static list's length, tiles, out_blocks."""
    from geomx_tpu.ops.bsc_pallas import place_visits, select_pack_shape
    tiles = len(p_cnt)
    _, out_blocks, out_rows = select_pack_shape(tiles * 32768, k)
    item, blk, total, start = jax.jit(
        lambda p, s: place_visits(p, s, k, out_blocks, out_rows * 128))(
            jnp.asarray(p_cnt, jnp.int32), jnp.asarray(s_cnt, jnp.int32))
    assert item.shape == blk.shape == (2 * tiles + out_blocks,)
    np.testing.assert_array_equal(
        np.asarray(start), np.cumsum([0] + list(p_cnt) + list(s_cnt)))
    total = int(total[0])
    item, blk = np.asarray(item), np.asarray(blk)
    # visits past the live ones repeat the last: no block moves
    assert (item[total:] == item[total - 1]).all()
    assert (blk[total:] == blk[total - 1]).all()
    return item[:total], blk[:total], tiles, out_blocks, out_rows * 128


@pytest.mark.parametrize("name,p_cnt,s_cnt,k", [
    # BERT-large's token embedding at a uniform 1%: 954 tiles, 39 blocks
    ("uniform", [328] * 954, [0] * 954, 312_546),
    # pairs in a quarter of the tiles only, a sentinel tail
    ("rows", [1200 if t % 4 == 0 else 0 for t in range(954)], [0] * 954,
     312_546),
    # more above the boundary than slots: the first tiles take them all
    ("overflow", [16000] * 954, [5] * 954, 312_546),
    # a tie here and there, after all primaries
    ("few-ties", [300] * 128, [1 if t in (3, 77) else 0 for t in range(128)],
     41_944),
    # nothing but ties
    ("ties-only", [0] * 128, [32768] * 128, 41_944),
    # nothing at all
    ("empty", [0] * 72, [0] * 72, 23_593),
])
def test_placement_visits_are_tiles_plus_blocks(name, p_cnt, s_cnt, k):
    """What the schedule is for: a class's (tile, output block) visits
    number at most tiles + out_blocks, a class with no element gets none,
    and ties cost what ties there are."""
    item, blk, tiles, out_blocks, slots = _place_visits(p_cnt, s_cnt, k)
    primary, ties = item < tiles, item >= tiles
    assert primary.sum() <= tiles + out_blocks
    assert ties.sum() <= tiles + out_blocks
    # every tile's new u and v are written: each primary item is there
    np.testing.assert_array_equal(np.unique(item[primary]), np.arange(tiles))
    # every output block is written, in order, its visits together
    assert blk[0] == 0 and blk[-1] == out_blocks - 1
    assert set(np.diff(blk)) <= {0, 1} and (np.diff(item) >= 0).all()
    # an item meets every block that one of its slots below k falls in
    start = np.cumsum([0] + list(p_cnt) + list(s_cnt))
    met = set(zip(item.tolist(), blk.tolist()))
    for i in range(2 * tiles):
        lo, hi = min(start[i], k), min(start[i + 1], k)
        if hi > lo:
            assert {(i, b) for b in range(lo // slots,
                                          (hi - 1) // slots + 1)} <= met
    # tie items: exactly those with a slot below k, none for a class with
    # no element
    live_ties = {tiles + t for t in range(tiles)
                 if min(start[tiles + t + 1], k) > min(start[tiles + t], k)}
    assert set(item[ties].tolist()) == live_ties
    if not any(s_cnt):
        assert not ties.any()
    assert len(item) <= tiles + len(live_ties) + out_blocks


def test_select_pack_threshold_probe_matches_reference(rng):
    """sampled_boundary_guv (gathers only) must equal the quantile of the
    dense momentum-corrected tensor at the same probe positions."""
    from geomx_tpu.ops.sampled_topk import boundary_position, sample_positions

    n, k = 30000, 300
    g = jnp.asarray(rng.normal(0, 1, n).astype(np.float32))
    u = jnp.asarray(rng.normal(0, 0.1, n).astype(np.float32))
    v = jnp.asarray(rng.normal(0, 0.2, n).astype(np.float32))

    @jax.jit
    def both(g, u, v):
        u2 = u * 0.9 + g
        v2 = v + u2
        pos = sample_positions(n)
        probe = jnp.sort(jnp.abs(v2)[jnp.asarray(pos, jnp.int32)])
        return (probe[boundary_position(len(pos), k, n)],
                sampled_boundary_guv(g, u, v, k))

    dense, gathered = both(g, u, v)
    assert float(dense) == float(gathered)


# ---------- the boundary probe: kernel against the gathers ----------

def _probe_case(shape, n, seed=0):
    """g, u, v of a bucket, u and v non-zero (``tools/boundary_timing.py``
    makes the same shapes on the chip)."""
    rng = np.random.default_rng(seed + n)
    if shape == "zero":
        return (jnp.zeros((n,), jnp.float32),) * 3
    g, u, v = (rng.normal(0, s, n).astype(np.float32) for s in (1, .1, .2))
    if shape == "rows":
        held = np.repeat(rng.uniform(size=-(-n // 128)) < 0.25, 128)[:n]
        held[:128] = True
        g, u, v = g * held, u * held, v * held
    return jnp.asarray(g), jnp.asarray(u), jnp.asarray(v)


@pytest.mark.parametrize("shape", ["uniform", "rows", "zero"])
@pytest.mark.parametrize("n", [1_024, 7_040, 8_192, 8_320, 32_768, 40_000,
                               133_120, 300_000])
def test_the_probe_gives_the_gathers_boundary(n, shape):
    """The door's boundary under the interpret hook (what a TPU runs:
    no fetch up to 8,192 elements, the streamed kernel above: one tile,
    several, a last tile that hangs over the end) is
    ``sampled_boundary_guv``'s float, bit for bit."""
    from geomx_tpu.ops import dispatch

    g, u, v = _probe_case(shape, n)
    k = -(-n // 100)
    want = jax.jit(lambda g, u, v: sampled_boundary_guv(g, u, v, k))(g, u, v)
    with kernels("interpret"):
        got = jax.jit(
            lambda g, u, v: dispatch.sampled_boundary(g, u, v, k))(g, u, v)
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()
    assert (float(want) == 0.0) == (shape == "zero")


@pytest.mark.parametrize("n", [8_320, 40_000, 300_000])
def test_the_probe_kernel_gives_every_gathered_sample(n):
    """Not the boundary alone: all 8,192 samples, in position order."""
    from geomx_tpu.ops.bsc_pallas import MOMENTUM
    from geomx_tpu.ops.sampled_topk import sample_positions

    g, u, v = _probe_case("uniform", n)
    pos = jnp.asarray(np.sort(sample_positions(n)), jnp.int32)
    want = jax.jit(lambda g, u, v: jnp.abs(
        v[pos] + (u[pos] * MOMENTUM + g[pos])))(g, u, v)
    got = bsc_boundary_probe(g, u, v, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _primitives(fn, *args):
    from geomx_tpu.analysis.core import walk_jaxpr
    return [(site.primitive, site.eqn)
            for site in walk_jaxpr(jax.make_jaxpr(fn)(*args),
                                   enter_opaque=True)]


def _probe_kernels(eqns):
    return [e.params["name"] for p, e in eqns if p == "pallas_call"]


@pytest.mark.parametrize("n", [1_024, 8_192])
def test_a_bucket_no_larger_than_the_probe_takes_no_fetch(n):
    """m == n: the positions are a permutation, so the dense expression
    is the sample: no gather and no kernel."""
    g = jnp.zeros((n,), jnp.float32)
    eqns = _primitives(
        lambda g, u, v: bsc_sampled_boundary(g, u, v, 11, interpret=True),
        g, g, g)
    assert not _probe_kernels(eqns)
    assert "gather" not in [p for p, _ in eqns]
    assert "sort" in [p for p, _ in eqns]


def test_a_bucket_above_the_threshold_keeps_the_gathers():
    """Streaming costs the bucket's bytes, gathering the probe's indices:
    above ``gather_above`` elements the gathers stay.  The threshold is
    the function's own argument (a constant in the engine's call)."""
    n, k = 40_000, 400
    g, u, v = _probe_case("uniform", n)

    def door(**kw):
        return lambda g, u, v: bsc_sampled_boundary(g, u, v, k,
                                                    interpret=True, **kw)

    streamed = _primitives(door(), g, u, v)
    assert _probe_kernels(streamed) == ["bsc_boundary_probe"]
    assert "gather" not in [p for p, _ in streamed]
    gathered = _primitives(door(gather_above=n - 1), g, u, v)
    assert not _probe_kernels(gathered)
    assert [p for p, _ in gathered].count("gather") == 3
    assert (np.asarray(jax.jit(door())(g, u, v)).tobytes()
            == np.asarray(jax.jit(door(gather_above=n - 1))(g, u, v)
                          ).tobytes())


@pytest.mark.parametrize("n", [8_192, 40_000])
def test_a_traced_k_gives_the_static_boundary_and_traces_once(n):
    """The control plane's ``eff_k`` is a traced scalar: same boundary,
    one trace however it changes, no shape that depends on it."""
    g, u, v = _probe_case("uniform", n)
    traces = []

    @jax.jit
    def traced(g, u, v, k):
        traces.append(1)
        return bsc_sampled_boundary(g, u, v, k, interpret=True)

    for k in (n // 100, n // 200, 1):
        want = jax.jit(lambda g, u, v: sampled_boundary_guv(g, u, v, k))(
            g, u, v)
        got = traced(g, u, v, jnp.int32(k))
        assert np.asarray(got).tobytes() == np.asarray(want).tobytes()
    assert len(traces) == 1


def test_the_probe_moves_its_values_as_exact_bf16_pieces():
    """Its one-hot products carry values, so they must be exact on the
    chip: the tile goes in as three bfloat16 pieces that sum to it (one
    MXU pass each, value x 1.0), never as float32 operands at the
    default precision, which the unit would round to bf16."""
    g = jnp.zeros((40_000,), jnp.float32)
    dots = [e for p, e in _primitives(
        lambda g: bsc_boundary_probe(g, g, g, interpret=True), g)
        if p == "dot_general"]
    assert len(dots) == 3
    for e in dots:
        assert [x.aval.dtype for x in e.invars] == [jnp.bfloat16] * 2
        assert e.params["preferred_element_type"] == jnp.float32
    x = np.random.default_rng(0).normal(0, 1, 4096).astype(np.float32)
    x[:4] = [0.0, 1e-30, 3.3e38, 1.0 + 2.0 ** -23]
    x = jnp.abs(jnp.asarray(x))
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    back = (hi.astype(jnp.float32) + mid.astype(jnp.float32)
            ) + lo.astype(jnp.float32)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(x))


@pytest.mark.parametrize("n", [8_320, 133_120, 1_048_576, 4_194_304,
                               8_388_608])
def test_the_probe_plan_covers_every_position_once(n):
    """Host arithmetic only: a tile's positions are consecutive in the
    sorted slab and its rows ``first .. first + count - 1`` hold them
    all; the visits are the rows plus the tiles that share one."""
    from geomx_tpu.ops.bsc_pallas import _TILE
    from geomx_tpu.ops.sampled_topk import sample_positions

    pos, first, count = probe_plan(n)
    assert pos.shape == (64, 128) and pos.dtype == np.int32
    flat = pos.reshape(-1)
    np.testing.assert_array_equal(flat, np.sort(sample_positions(n)))
    assert len(np.unique(flat)) == 8192 and flat.max() < n
    tiles = -(-n // _TILE)
    assert first.shape == count.shape == (tiles,)
    for t in range(tiles):
        slots = np.nonzero(flat // _TILE == t)[0]
        if len(slots) == 0:
            assert count[t] == 0
            continue
        assert first[t] == slots[0] // 128
        assert first[t] + count[t] - 1 == slots[-1] // 128
    assert 64 <= count.sum() <= 64 + tiles


def test_the_probe_lowers_to_tpu_mosaic_without_a_device():
    from jax import export as jax_export

    g = jnp.zeros((40_000,), jnp.float32)
    exp = jax_export.export(
        jax.jit(lambda g, u, v: bsc_sampled_boundary(g, u, v, 400)),
        platforms=("tpu",))(g, g, g)
    assert "tpu_custom_call" in exp.mlir_module()


# ---------- scatter-add decompress parity ----------

def test_scatter_add_parity_with_collisions():
    """Integer-representable values make every collision sum exact, so
    the fused matmul accumulate must be bit-identical to the jnp
    scatter-add regardless of reduction order."""
    n = 3000
    idx = jnp.asarray([5, 100, 100, 2999, -1, -1, 7, 5, 0, 2999],
                      jnp.int32)
    vals = jnp.asarray([1.0, 2.0, 3.0, -4.0, 9.0, 0.0, 0.5, 0.25, 8.0,
                        1.0], jnp.float32)
    cj, cf = _pair()
    ref = jax.jit(lambda a, b: cj.decompress(a, b, n))(vals, idx)
    fus = jax.jit(lambda a, b: cf.decompress(a, b, n))(vals, idx)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(fus))


@pytest.mark.parametrize("n,m", [(128, 4), (1000, 700), (65536, 2624)])
def test_scatter_add_parity_random(rng, n, m):
    idx = jnp.asarray(rng.randint(-1, n, m).astype(np.int32))
    vals = jnp.asarray(np.round(rng.normal(0, 8, m)).astype(np.float32))
    cj, cf = _pair()
    ref = jax.jit(lambda a, b: cj.decompress(a, b, n))(vals, idx)
    fus = jax.jit(lambda a, b: cf.decompress(a, b, n))(vals, idx)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(fus))


def test_scatter_add_all_sentinel():
    out = bsc_scatter_add(jnp.zeros((64,)), jnp.full((64,), -1, jnp.int32),
                          500, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.zeros(500))


from geomx_tpu.ops.bsc_pallas import _CHUNK, _OUT_ROWS  # noqa: E402

_BLOCK = _OUT_ROWS * 128   # elements of one decompress output block


def _party_run(rng, n, k, real):
    """One party's wire format: ``real`` distinct ascending indices, the
    last tenth of them a second ascending run (the ties), then
    sentinels up to k."""
    idx = np.full(k, -1, np.int32)
    picked = rng.choice(n, real, replace=False)
    ties = real // 10
    idx[:real - ties] = np.sort(picked[:real - ties])
    idx[real - ties:real] = np.sort(picked[real - ties:])
    return idx


def _decompress_cases():
    rng = np.random.RandomState(25)
    cases = {}
    n = 5 * _BLOCK
    cases["one-ascending-run"] = (
        n, np.sort(rng.choice(n, 3000, replace=False)).astype(np.int32))
    for parties in (1, 2, 4):
        # every party draws from the same tenth of the bucket: the runs
        # collide with each other, as parties' selections do
        cases[f"{2 * parties}-runs-{parties}-parties"] = (n, np.concatenate(
            [_party_run(rng, n // 10, 1400, 1300) * 10 % n
             for _ in range(parties)]))
    cases["unsorted-as-top-k-hands-them-over"] = (
        n, rng.randint(0, n, 2600).astype(np.int32))
    cases["all-sentinels-many-chunks"] = (n, np.full(1300, -1, np.int32))
    cases["every-pair-in-one-block"] = (
        n, (2 * _BLOCK + np.sort(rng.choice(_BLOCK, 1500, replace=False))
            ).astype(np.int32))
    cases["first-and-last-block-only"] = (n, np.concatenate(
        [np.sort(rng.choice(_BLOCK, 700, replace=False)),
         4 * _BLOCK + np.sort(rng.choice(_BLOCK, 700, replace=False)),
         np.full(136, -1)]).astype(np.int32))
    # 40 pairs in block 0, 300 in block 1, the chunk's other 172 and the
    # next chunk in block 2: chunk 0 straddles three blocks
    cases["a-chunk-straddles-three-blocks"] = (n, np.concatenate(
        [np.sort(rng.choice(_BLOCK, 40, replace=False)),
         _BLOCK + np.sort(rng.choice(_BLOCK, 300, replace=False)),
         2 * _BLOCK + np.sort(rng.choice(_BLOCK, 600, replace=False))]
    ).astype(np.int32))
    cases["n-below-one-block"] = (
        7040, np.sort(rng.choice(7040, 1100, replace=False)).astype(np.int32))
    odd = 3 * _BLOCK + 8 * 128 * 5 + 77     # whole tiles short of a block
    cases["n-not-a-multiple-of-the-block"] = (
        odd, np.append(np.sort(rng.choice(odd, 1500, replace=False)),
                       odd - 1).astype(np.int32))
    cases["m-not-a-multiple-of-the-chunk"] = (
        n, np.sort(rng.choice(n, 2 * _CHUNK + 13, replace=False)
                   ).astype(np.int32))
    cases["one-chunk-many-blocks"] = (
        n, np.sort(rng.choice(n, 300, replace=False))[::-1].astype(np.int32))
    return cases


_DECOMPRESS_CASES = _decompress_cases()


@pytest.mark.parametrize("case", sorted(_DECOMPRESS_CASES))
def test_scatter_add_against_the_jnp_oracle(case):
    """Exact scatter-add whatever the pairs' order and wherever they
    fall: whole-number values make every collision sum exact, so the
    result is bit-identical to ``zeros(n).at[idx].add(vals)`` in any
    summation order."""
    n, idx = _DECOMPRESS_CASES[case]
    rng = np.random.RandomState(len(case))
    vals = np.where(idx >= 0, np.round(rng.normal(0, 8, idx.shape[0])),
                    3.0).astype(np.float32)   # a sentinel's value is dropped
    got = bsc_scatter_add(jnp.asarray(vals), jnp.asarray(idx), n,
                          interpret=True)
    want = jnp.zeros((n,), jnp.float32).at[
        jnp.where(idx >= 0, idx, n)].add(jnp.asarray(vals), mode="drop")
    assert got.shape == (n,)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _visits(idx, n):
    """The schedule ``bsc_scatter_add`` computes for ascending ``idx``,
    as numpy: (blk, chk) of the live visits, blocks, chunks."""
    from geomx_tpu.ops.bsc_pallas import _SENTINEL_KEY, scatter_visits
    blocks = -(-n // _BLOCK)
    chunks = -(-idx.shape[0] // _CHUNK)
    key = np.full(chunks * _CHUNK, _SENTINEL_KEY, np.int32)
    key[:idx.shape[0]] = np.where(idx >= 0, idx, _SENTINEL_KEY)
    blk, chk, total = jax.jit(
        lambda k: scatter_visits(k, blocks, _BLOCK))(
            jnp.asarray(key.reshape(chunks, _CHUNK)))
    assert blk.shape == chk.shape == (blocks + chunks,)
    total = int(total[0])
    return (np.asarray(blk)[:total], np.asarray(chk)[:total], blocks, chunks,
            key.reshape(chunks, _CHUNK))


@pytest.mark.parametrize("n,k,emitted", [
    (31_254_528, 312_546, 0.957),   # BERT-large's token embedding bucket
    (4_194_304, 41_944, 1.0),       # an FFN matrix
    (2_359_296, 23_593, 0.5),       # ResNet-18 layer4, half sentinels
])
def test_decompress_visits_are_the_sum_not_the_product(n, k, emitted):
    """What the schedule is for: (block, chunk) visits in proportion to
    ``out_blocks + chunks``, where the parent's grid made their product
    (1,165,788 steps for the embedding bucket).  Read from the visit
    list the implementation computes; the kernel is not run."""
    rng = np.random.RandomState(k % 1000)
    real = int(k * emitted)
    idx = np.full(k, -1, np.int32)
    idx[:real] = np.sort(rng.choice(n, real, replace=False))
    blk, chk, blocks, chunks, key = _visits(idx, n)
    assert len(blk) <= blocks + 2 * chunks
    assert len(blk) <= blocks + chunks          # the bound it is built to
    # every block is visited, in order, its visits next to each other
    assert blk[0] == 0 and blk[-1] == blocks - 1
    assert set(np.diff(blk)) <= {0, 1} and set(np.diff(chk)) <= {0, 1}
    # every chunk meets every block one of its pairs falls into
    want = {(int(b), c) for c in range(chunks)
            for b in np.unique(key[c][key[c] < n] // _BLOCK)}
    assert want <= set(zip(blk.tolist(), chk.tolist()))


def test_decompress_visits_with_nothing_to_scatter():
    """All sentinels: one chunk still walks every block, to zero it."""
    blk, chk, blocks, chunks, _ = _visits(np.full(2000, -1, np.int32),
                                          5 * _BLOCK)
    np.testing.assert_array_equal(blk, np.arange(blocks))
    assert set(chk) == {0}


def test_value_carrying_matmuls_are_full_precision():
    """Found on a v5e (PR 21), invisible in interpret mode: at the MXU's
    default precision an fp32 ``dot_general`` rounds its operands to
    bf16, so the one-hot matmuls that move VALUES (the scatter-add, the
    select kernel's compaction before PR 28) returned every value up to
    2.9e-3 off.  They must ask for ``Precision.HIGHEST`` (value x 1.0 is
    then exact); the 0/1-operand prefix-sum matmuls need not."""
    from geomx_tpu.analysis.core import walk_jaxpr

    def dot_precisions(fn, *args):
        return [site.eqn.params["precision"]
                for site in walk_jaxpr(jax.make_jaxpr(fn)(*args),
                                       enter_opaque=True)
                if site.primitive == "dot_general"]

    highest = (jax.lax.Precision.HIGHEST,) * 2
    # the decompress: every matmul in it carries values, however many
    # the schedule makes; on pairs enough for the staircase schedule too
    for m in (64, 2048):
        f, i = jnp.zeros((m,), jnp.float32), jnp.zeros((m,), jnp.int32)
        dec = dot_precisions(lambda v, ix: bsc_scatter_add(v, ix, n=65536),
                             f, i)
        assert dec and set(dec) == {highest}, dec
    # the select/pack moves its values by rolls and selects: its only
    # matmuls are the 0/1 prefix sums, exact at the default precision,
    # in the one-tile kernel and in the placing pass alike
    for n in (4096, 100_000):
        g = jnp.zeros((n,), jnp.float32)
        sel = dot_precisions(
            lambda a, t: bsc_select_pack(a, a, a, t, k=n // 100),
            g, jnp.float32(0.5))
        assert sel and highest not in sel, sel


# ---------- round trip through the compressed all-reduce ----------

def test_fused_bsc_allreduce_matches_jnp_path(topo2x4, mesh2x4):
    """End-to-end through the dc-tier collective: the fused compressor
    must produce the same aggregate and carry the same error-feedback
    state as the jnp path (allclose: parties' pairs may collide, and
    collision order differs between scatter and matmul accumulate)."""
    from tests.test_compression import _run_dc_allreduce

    rng = np.random.RandomState(11)
    g = rng.normal(0, 0.8, size=(2, 8192)).astype(np.float32)
    out_j, st_j = _run_dc_allreduce(
        BiSparseCompressor(0.01, min_sparse_size=1), g, topo2x4, mesh2x4)
    with kernels("interpret"):
        out_f, st_f = _run_dc_allreduce(
            BiSparseCompressor(0.01, min_sparse_size=1), g, topo2x4, mesh2x4)
    np.testing.assert_allclose(out_f, out_j, atol=1e-6)
    for a, b in zip(jax.tree.leaves(st_j), jax.tree.leaves(st_f)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


# ---------- fused bucket flatten/unflatten ----------

def test_fused_bucket_flatten_roundtrip_parity(rng):
    leaves = [jnp.asarray(rng.normal(0, 1, s).astype(np.float32)).astype(d)
              for s, d in
              [((16, 8), jnp.float32), ((5,), jnp.float32),
               ((300,), jnp.float32), ((7, 3, 2), jnp.bfloat16),
               ((1000,), jnp.float32), ((1,), jnp.float32)]]
    bj = GradientBucketer(leaves, bucket_bytes=2048)
    bf = _Under(bj, "interpret")
    fb, jb = bf.flatten(leaves), bj.flatten(leaves)
    assert len(fb) == len(jb) == bj.num_buckets
    for a, b in zip(fb, jb):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    fl, jl = bf.unflatten(fb), bj.unflatten(jb)
    for a, b, leaf in zip(fl, jl, leaves):
        assert a.shape == leaf.shape and a.dtype == leaf.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fused_flatten_wide_pad_to(rng):
    """pad_to is a caller knob: tails larger than the 128-lane default
    must still zero-fill correctly (the zeros DMA source scales with the
    largest tail)."""
    leaves = [jnp.asarray(rng.normal(0, 1, s).astype(np.float32))
              for s in (700, 3, 129)]
    bj = GradientBucketer(leaves, bucket_bytes=1 << 20, pad_to=512)
    bf = _Under(bj, "interpret")
    for a, b in zip(bf.flatten(leaves), bj.flatten(leaves)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fused_bucketed_compressor_matches_jnp(topo2x4, mesh2x4):
    """The BucketedCompressor with fused (un)flatten produces the same
    dc aggregate as the jnp layout path — the layout kernels are a pure
    permutation, so this is bit-exact."""
    from tests.test_compression import _run_dc_allreduce
    from geomx_tpu.compression import NoCompressor
    from geomx_tpu.compression.bucketing import BucketedCompressor

    rng = np.random.RandomState(5)
    g = rng.normal(0, 1, size=(2, 3000)).astype(np.float32)
    out_j, _ = _run_dc_allreduce(
        BucketedCompressor(NoCompressor(), 4096), g, topo2x4, mesh2x4)
    with kernels("interpret"):
        out_f, _ = _run_dc_allreduce(
            BucketedCompressor(NoCompressor(), 4096), g, topo2x4, mesh2x4)
    np.testing.assert_array_equal(out_f, out_j)


# ---------- TPU Mosaic cross-lowering guards ----------

def test_bsc_kernels_lower_to_tpu_mosaic_without_a_device():
    """Same guard as the flash/2-bit kernels: lower against abstract
    shapes for the TPU platform on the CPU host, so a kernel edit that
    breaks Mosaic tiling fails in CI, not on chip."""
    from jax import export as jax_export

    n, k = 8192, 82
    g = jnp.zeros((n,), jnp.float32)

    def sel(g, u, v, thr):
        return bsc_select_pack(g, u, v, thr, k)

    exp = jax_export.export(jax.jit(sel), platforms=("tpu",))(
        g, g, g, jnp.float32(0.5))
    assert "tpu_custom_call" in exp.mlir_module()

    def dec(vals, idx):
        return bsc_scatter_add(vals, idx, n)

    exp = jax_export.export(jax.jit(dec), platforms=("tpu",))(
        jnp.zeros((2 * k,), jnp.float32), jnp.zeros((2 * k,), jnp.int32))
    assert "tpu_custom_call" in exp.mlir_module()


def test_bucket_kernels_lower_to_tpu_mosaic_without_a_device(rng):
    from jax import export as jax_export
    from geomx_tpu.ops.bucket_pallas import fused_flatten, fused_unflatten

    leaves = [jnp.asarray(rng.normal(0, 1, s).astype(np.float32))
              for s in (130, 5, 1000, 64)]
    bk = GradientBucketer(leaves, bucket_bytes=4096)
    layout = tuple((b, off, size) for (b, off), size in
                   zip(bk.assignments, bk.leaf_sizes))

    def flat(*ls):
        return fused_flatten(ls, layout, tuple(bk.bucket_sizes))

    exp = jax_export.export(jax.jit(flat), platforms=("tpu",))(*leaves)
    assert "tpu_custom_call" in exp.mlir_module()

    def unflat(*bs):
        return fused_unflatten(bs, layout, tuple(bk.leaf_sizes))

    exp = jax_export.export(jax.jit(unflat), platforms=("tpu",))(
        *[jnp.zeros((s,), jnp.float32) for s in bk.bucket_sizes])
    assert "tpu_custom_call" in exp.mlir_module()


# ---------- lowered-HLO structure regression ----------

def _largest_before_the_kernel(fn, *args):
    """Elements of the largest array any equation produces ahead of the
    first Pallas kernel (all of them where there is none): what a path
    sets up in HBM beside its output."""
    import itertools

    from geomx_tpu.analysis.core import walk_jaxpr

    sites = itertools.takewhile(
        lambda site: site.primitive != "pallas_call",
        walk_jaxpr(jax.make_jaxpr(fn)(*args)))
    # a `jit` equation wraps the equations that follow; its results are
    # theirs
    return max(int(np.prod(v.aval.shape)) for site in sites
               if site.primitive not in ("jit", "pjit")
               for v in site.eqn.outvars if hasattr(v.aval, "shape"))


def test_fused_paths_remove_dense_intermediates(rng):
    """The structural claim of the fused kernel layer, checked on the
    shared lowered-HLO assertions library (geomx_tpu/analysis/hlo.py): the ops
    that materialize a dense gradient-sized intermediate in the unfused
    graphs (scatter, cumsum expansion, per-leaf concatenate/slice
    copies) must be ABSENT from the fused graphs, which instead carry
    one tpu_custom_call per kernel."""
    from geomx_tpu.analysis.hlo import (assert_dense_intermediates_removed,
                                        compare_paths)

    n = 20000
    cj, _ = _pair(ratio=0.01)
    # NON-interpret fused compressor: the HLO must contain the real
    # custom call (interpret mode traces the kernel as while loops)
    cf = _Under(cj, "native")
    g = jnp.asarray(rng.normal(0, 1, n).astype(np.float32))
    z = jnp.zeros((n,), jnp.float32)
    m = 4 * cj.k_for(n)
    vals = jnp.zeros((m,), jnp.float32)
    idx = jnp.zeros((m,), jnp.int32)

    sel = compare_paths(
        lambda a, b, c: cj.compress(a, b, c),
        lambda a, b, c: cf.compress(a, b, c), g, z, z,
        dense_ops=("scatter", "reduce_window", "while",
                   "dynamic_update_slice"))
    assert_dense_intermediates_removed(sel)
    # the small-tensor ops both paths share (sample sort/gathers, pad
    # concats) stay; everything dense-sized is gone
    assert sel["dense_unfused"] >= 3 and sel["dense_fused"] == 0, sel

    # the decompress: no XLA scatter, and nothing of the bucket's size
    # beside the output.  Its schedule sorts the m PAIRS where they come
    # out of order and works on per-chunk arrays; what the guard forbids
    # is a sort, scan or scatter over the n ELEMENTS
    dec = compare_paths(
        lambda a, b: cj.decompress(a, b, n),
        lambda a, b: cf.decompress(a, b, n), vals, idx,
        dense_ops=("scatter",))
    assert_dense_intermediates_removed(dec)
    assert _largest_before_the_kernel(
        lambda a, b: cf.decompress(a, b, n), vals, idx) <= m + 512
    assert _largest_before_the_kernel(
        lambda a, b: cj.decompress(a, b, n), vals, idx) == n

    leaves = [jnp.asarray(rng.normal(0, 1, s).astype(np.float32))
              for s in (432, 16, 2304, 16, 9216, 64, 640, 10)]
    flat_v = compare_paths(
        lambda *ls: GradientBucketer(leaves, 65536).flatten(list(ls)),
        lambda *ls: _Under(GradientBucketer(leaves, 65536),
                           "native").flatten(list(ls)), *leaves,
        dense_ops=("concatenate", "dynamic_update_slice"))
    assert_dense_intermediates_removed(flat_v)
    assert flat_v["fused"]["tpu_custom_calls"] == 1


# ---------- the door (ops/dispatch.py) ----------

def _bucket_allreduce_jaxpr(spec="bsc,0.01"):
    """The jaxpr of a default ``spec`` bucket allreduce as a cell builds
    it (spec string -> get_compressor -> bucketed)."""
    from geomx_tpu.compression import get_compressor
    from geomx_tpu.compression.bucketing import maybe_bucketed

    comp = maybe_bucketed(get_compressor(spec))
    grads = [jnp.zeros((300, 40)), jnp.zeros((77,)), jnp.zeros((40000,))]
    state = comp.init_state(grads)
    return str(jax.make_jaxpr(
        lambda g, s: comp.allreduce(g, s, "dc", 1))(grads, state))


@pytest.mark.parametrize("name,value", [
    ("GEOMX_BSC_SELECT", "exact"), ("GEOMX_BSC_APPROX_TOPK", "0"),
    ("GEOMX_FUSED_KERNELS", "0"), ("GEOMX_TWOBIT_PALLAS", "0"),
    ("GEOMX_FLASH_ATTN", "0")])
def test_the_engine_reads_no_environment(monkeypatch, name, value):
    """The five names that used to choose a code path choose nothing."""
    want = _bucket_allreduce_jaxpr()
    monkeypatch.setenv(name, value)
    assert _bucket_allreduce_jaxpr() == want
    assert "top_k" not in want and "approx_top_k" not in want


@pytest.mark.parametrize("sizes", [
    pytest.param([(50, 100), (2000,), (40,)], id="one-tile"),
    pytest.param([(300, 300), (77,), (40000,)], id="several-tiles"),
    pytest.param([(20, 20), (77,)], id="under-min_sparse_size"),
])
def test_the_door_gives_the_kernels_the_jnp_forms_results(rng, sizes):
    """A cell's bucket allreduce on the CPU default (jnp forms) and under
    the interpret hook (the kernels a TPU runs): outputs and (u, v) bit
    for bit, twice over so the error feedback is exercised."""
    from geomx_tpu.compression.bucketing import BucketedCompressor

    comp = BucketedCompressor(BiSparseCompressor(0.01))
    grads = [jnp.asarray(rng.normal(0, 1, s).astype(np.float32))
             for s in sizes]

    def run():
        fn = jax.jit(lambda g, s: comp.allreduce(g, s, "dc", 1))
        out, state = fn(grads, comp.init_state(grads))
        return fn(out, state)

    want = run()
    with kernels("interpret"):
        got = run()
    sparse = sum(int(np.prod(s)) for s in sizes) >= 1024
    assert bool(jax.tree.leaves(want[1])) == sparse
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("key", ["select=sampled", "approx=1", "fused=1"])
def test_the_spec_grammar_has_no_key_that_picks_an_implementation(key):
    from geomx_tpu.compression import get_compressor

    with pytest.raises(ValueError, match="valid keys.*min_sparse_size"):
        get_compressor(f"bsc,0.02,{key}")
