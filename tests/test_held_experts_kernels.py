"""The held experts' row scatter-add (`ops/moe_rows_pallas.py`) against its
jnp form, in interpret mode on the CPU: tokens that sit several times in
one tile across run boundaries, rows of the cells' widths, and
`held_experts` through the door (`ops/dispatch.py`) with the kernel
against without it.  Interpret mode completes a copy at its
start, so it holds the arithmetic, the tails' masks and the segment walk,
not the overlap of copies in flight: the chip does (`chip_smoke.py`,
`tools/held_experts_timing.py --pieces`)."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from geomx_tpu.ops import dispatch, moe_rows_pallas as rows_ops  # noqa: E402
from geomx_tpu.ops.held_experts import held_experts  # noqa: E402

TOKENS = 96


# several tiles at the tests' small pools
TILE = 32


# places of each run (4 or 8 experts) in a pool of 128 places, tiles of 32
RUNS = {
    "no_valid_place": [0, 0, 0, 0],
    "one_expert_takes_every_token": [0, 96, 0, 0],
    "the_end_inside_a_tile": [3, 0, 50, 20],
    "a_token_twice_in_one_tile": [10, 12, 0, 0],
    "a_token_three_times_in_one_tile": [9, 9, 9, 0],
    "a_token_eight_times_in_one_tile": [4, 4, 4, 4, 4, 4, 4, 4],
    "runs_that_cross_tiles": [40, 30, 37, 21],
}


@pytest.mark.parametrize("d", [256, 2048, 2304, 96])
@pytest.mark.parametrize("case", sorted(RUNS))
def test_the_scatter_add_keeps_every_addend(case, d):
    """The same few tokens in every run, so that a tile holds one of them
    as often as it holds runs: a walk that read, added and wrote a whole
    tile at once would lose all but one addend.  Addends are small whole
    numbers: their sum is exact in any order.  Row widths: the cells'
    2,048 (16 lane tiles) and 2,304 (18), and one that is no whole lane
    tile."""
    rng = np.random.default_rng(len(case))
    sizes = RUNS[case]
    places = 128
    few = rng.choice(TOKENS, max(max(sizes), 1), replace=False)
    runs = [few[:size] if size <= 12 else
            rng.choice(TOKENS, size, replace=False) for size in sizes]
    pad = TOKENS + np.arange(places - sum(sizes))
    token = jnp.asarray(np.concatenate(runs + [pad]), jnp.int32)
    out = rng.integers(-8, 9, (places, d)).astype(np.float32)
    out[sum(sizes):] = 0.0
    y = jnp.asarray(rng.integers(-8, 9, (TOKENS, d)), jnp.float32)
    got = rows_ops.moe_row_scatter_add(
        y, jnp.asarray(out), token, jnp.asarray(sizes, jnp.int32),
        interpret=True, max_tile=TILE)
    want = rows_ops.row_scatter_add_ref(y, jnp.asarray(out), token, None)
    np.testing.assert_array_equal(got, want)
    if "times" in case or "twice" in case:
        times = np.bincount(np.asarray(token), minlength=TOKENS + places)
        assert times[:TOKENS].max() == sum(size > 0 for size in sizes)


def test_the_door_keeps_xlas_scatter_add_where_slabs_would_pad(monkeypatch):
    """A row of 2,048 floats is whole (8, 128) tiles as a slab, one of
    2,304 is not (18 sublanes pad to 24; the Kimi cell's peak memory rose
    10% with the kernel in): the width decides at the door, under every
    kernel mode."""
    assert rows_ops.slabs_are_whole(2048) and rows_ops.slabs_are_whole(1024)
    assert not rows_ops.slabs_are_whole(2304)
    assert not rows_ops.slabs_are_whole(256)
    taken = []
    monkeypatch.setattr(
        rows_ops, "moe_row_scatter_add",
        lambda y, *rest, interpret: taken.append(y.shape[1]) or y)
    token, sizes = jnp.zeros((16,), jnp.int32), jnp.zeros((2,), jnp.int32)
    for d in (2048, 2304):
        y, out = jnp.zeros((8, d)), jnp.zeros((16, d))
        dispatch.row_scatter_add(y, out, token, sizes)      # no kernel mode
        with dispatch.kernels("interpret"):
            dispatch.row_scatter_add(y, out, token, sizes)
    assert taken == [2048]


def test_a_tile_size_follows_the_shape_and_the_budget():
    # the cells: 32,768 places of 2,048 and 8,192 of 2,304
    assert rows_ops.tile_rows(32768, 2048) == 512
    assert rows_ops.tile_rows(8192, 2304) == 256
    assert rows_ops.tile_rows(1024, 2048) == 512
    assert rows_ops.tile_rows(1024, 2048, 32) == 32
    assert rows_ops.tile_rows(48, 256) == 16
    with pytest.raises(ValueError, match="whole tiles"):
        rows_ops.tile_rows(100, 256)


def routing(rng, tokens, top_k, router):
    return jnp.asarray(np.stack([rng.choice(router, top_k, replace=False)
                                 for _ in range(tokens)]), jnp.int32)


@pytest.mark.parametrize("dtype,tolerance", [(jnp.float32, 3e-5),
                                             (jnp.bfloat16, 3e-5)])
@pytest.mark.parametrize("rows,pool", [(16, None), (16, 32), (8, 64)])
def test_held_experts_through_the_door(dtype, tolerance, rows, pool):
    """`held_experts` traced under `kernels("interpret")` (the row kernel
    and the grouped products interpreted) against `kernel_mode() is None`
    (the jnp scatter-add): y, counts, dropped and all five gradients, at
    `tests/test_kimi_ops.py`'s tolerance (both sides run the same products
    on the same rows: what may differ is the order of a token's addends).
    A first pool that holds everything, one that does not (later pools in
    the `while`), and tiles of 8."""
    rng = np.random.default_rng(rows + (pool or 0))
    tokens, d, width, held, top_k, router = 96, 1024, 64, 4, 4, 12
    assert rows_ops.slabs_are_whole(d)      # the door takes the kernel
    x = jnp.asarray(rng.standard_normal((tokens, d)), dtype)
    idx = routing(rng, tokens, top_k, router)
    w = jnp.asarray(rng.uniform(0.1, 0.5, (tokens, top_k)), jnp.float32)
    mats = [jnp.asarray(rng.standard_normal(shape) * 0.1, jnp.float32)
            for shape in ((held, d, width), (held, d, width),
                          (held, width, d))]
    r = jnp.asarray(rng.standard_normal((tokens, d)), jnp.float32)

    def loss(x_, w_, gate, up, down):
        y, counts, dropped = held_experts(x_, idx, w_, gate, up, down, 0,
                                          rows, None, pool)
        return jnp.sum(y * r), (y, counts, dropped)

    run = lambda: jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(x, w, *mats)
    assert dispatch.kernel_mode() is None
    (_, (y0, counts0, dropped0)), grads0 = run()
    with dispatch.kernels("interpret"):
        (_, (y1, counts1, dropped1)), grads1 = run()
    np.testing.assert_array_equal(counts0, counts1)
    assert int(dropped0) == int(dropped1) == 0
    assert int(jnp.sum(counts0)) > (pool or 0)
    for got, want in zip((y1, *grads1), (y0, *grads0)):
        want = np.asarray(want, np.float32)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), want,
            atol=tolerance * max(1.0, float(np.max(np.abs(want)))))


# --- the plan: token and weight ride its one sort -------------------------

def _routing_with(rng, tokens, top_k, held, router, share):
    """idx [T, k]: distinct experts a token; `share` none / some / all of
    the assignments on the `held` experts from `offset` on."""
    if share == "all":
        return jnp.asarray(np.stack([rng.permutation(held)[:top_k]
                                     for _ in range(tokens)]), jnp.int32), 0
    offset = 3
    idx = np.stack([rng.choice(router, top_k, replace=False)
                    for _ in range(tokens)])
    if share == "none":
        away = (idx >= offset) & (idx < offset + held)
        idx = np.where(away, idx + router, idx)
    return jnp.asarray(idx, jnp.int32), offset


def _plan_by_argsort_and_gather(idx, weights, num_held, offset, rows, pool):
    """`_plan` as it stood before the payload rode the sort (PR 38):
    argsort, then the weights read in sorted order by a 1-D gather."""
    from geomx_tpu.ops.held_experts import _pools
    t, k = idx.shape
    local = idx.reshape(-1) - offset
    held = (local >= 0) & (local < num_held)
    key = jnp.where(held, local, num_held).astype(jnp.int32)
    order = jnp.argsort(key, stable=True)
    counts = jnp.sum(key[:, None] == jnp.arange(num_held)[None, :], axis=0,
                     dtype=jnp.int32)
    pad = (0, _pools(num_held, rows, t * k, pool)[2] - t * k)
    return {"token": jnp.pad((order // k).astype(jnp.int32), pad),
            "weight": jnp.pad(
                weights.reshape(-1)[order].astype(jnp.float32), pad),
            "order": order, "counts": counts, "ends": jnp.cumsum(counts)}


def _bits(a):
    a = np.asarray(a)
    return a.view(np.uint32) if a.dtype == np.float32 else a


PLAN_CASES = [(gated, top_k, share, tokens)
              for gated in (True, False) for top_k in (8, 22)
              for share in ("none", "some", "all")
              # T k a multiple of the pools' places, and not
              for tokens in (16, 13)]


@pytest.mark.parametrize(
    "gated,top_k,share,tokens", PLAN_CASES,
    ids=[f"{'gated' if g else 'ungated'}-k{k}-{s}_held-T{t}"
         for g, k, s, t in PLAN_CASES])
def test_the_plans_one_sort_equals_argsort_and_gather(gated, top_k, share,
                                                      tokens, monkeypatch):
    """`_plan`'s token, weight, order, counts and ends against the argsort
    and gather it replaced, and `dweights` (brought back by a sort)
    against the scatter `zeros.at[order].set(dw)`, bit for bit: the
    weights are moved, never recomputed.  8 and 22 experts a token (the
    Trinity / Kimi cells' and the Nemotron cell's), no, some and every
    assignment held, T k whole pools (16 tokens) and not (13)."""
    from geomx_tpu.ops import held_experts as module
    rng = np.random.default_rng(top_k * tokens + len(share))
    held, router, rows, pool, d, width = 24, 64, 8, 32, 128, 16
    idx, offset = _routing_with(rng, tokens, top_k, held, router, share)
    w = jnp.asarray(rng.uniform(0.1, 0.5, (tokens, top_k)), jnp.float32)
    got = jax.jit(lambda i, w_: module._plan(i, w_, held, offset, rows, pool)
                  )(idx, w)
    want = jax.jit(lambda i, w_: _plan_by_argsort_and_gather(
        i, w_, held, offset, rows, pool))(idx, w)
    assert int(got.pop("tokens")) == tokens
    assert sorted(got) == sorted(want)
    for name in want:
        assert got[name].dtype == want[name].dtype, name
        np.testing.assert_array_equal(_bits(got[name]), _bits(want[name]),
                                      err_msg=name)
    arrived = int(want["ends"][-1])
    assert {"none": arrived == 0, "some": 0 < arrived < idx.size,
            "all": arrived == idx.size}[share]
    assert (idx.size % pool == 0) == (tokens == 16)

    # dweights: the sort back against the scatter, through the layer
    x = jnp.asarray(rng.standard_normal((tokens, d)), jnp.float32)
    mats = [jnp.asarray(rng.standard_normal(shape) * 0.1, jnp.float32)
            for shape in ((held, d, width), (held, d, width),
                          (held, width, d))]
    if not gated:
        mats[0] = None
    r = jnp.asarray(rng.standard_normal((tokens, d)), jnp.float32)

    # one trace of the layer's own forward and backward rules gives
    # dweights as `_bwd` brings them back and, from the sort's own
    # operands, the scatter form of the same sorted gradient
    scattered, lax_sort = [], jax.lax.sort

    def sort(operands, **kw):
        if len(operands) == 2:
            order, dw = operands
            scattered.append(jnp.zeros(dw.shape, dw.dtype).at[order].set(
                dw, unique_indices=True))
        return lax_sort(operands, **kw)

    def both(w_):
        _, res = module._fwd(x, idx, w_, *mats, offset, rows, None, pool)
        grads = module._bwd(offset, rows, None, pool, res, (r,))
        return grads[2], scattered[-1].reshape(w_.shape)

    monkeypatch.setattr(module.lax, "sort", sort)
    dw_sorted, dw_scattered = jax.jit(both)(w)
    assert len(scattered) == 1
    np.testing.assert_array_equal(_bits(dw_sorted), _bits(dw_scattered))
    assert bool(jnp.any(dw_sorted != 0)) == (share != "none")


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else (
                    value,):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


@pytest.mark.parametrize("gated", [True, False], ids=["gated", "ungated"])
def test_no_gather_or_scatter_over_the_routed_assignments(gated):
    """The jaxpr of `held_experts`, forward and backward, holds no gather
    and no scatter with an operand of T k elements (or of the T k places
    padded to whole pools): what the layer pays by the routed assignment
    is two sorts, and the row moves are over a pool's places."""
    from geomx_tpu.ops.held_experts import _pools
    rng = np.random.default_rng(5)
    tokens, d, width, held, top_k, router, rows, pool = 40, 128, 16, 4, 22, \
        64, 8, 32
    idx = routing(rng, tokens, top_k, router)
    w = jnp.asarray(rng.uniform(0.1, 0.5, (tokens, top_k)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((tokens, d)), jnp.float32)
    mats = [jnp.asarray(rng.standard_normal(shape) * 0.1, jnp.float32)
            for shape in ((held, d, width), (held, d, width),
                          (held, width, d))]
    if not gated:
        mats[0] = None
    over = {tokens * top_k, _pools(held, rows, tokens * top_k, pool)[2]}
    assert not over & {tokens * d, pool * d, 2 * rows * d}

    def loss(x_, w_, *m):
        return jnp.sum(held_experts(x_, idx, w_, *m, 0, rows, None, pool)[0])

    argnums = tuple(i for i, m in enumerate((x, w, *mats)) if m is not None)
    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss, argnums=argnums))(
        x, w, *mats)
    moves, sorts = [], 0
    for eqn in _equations(jaxpr.jaxpr):
        name = eqn.primitive.name
        sorts += name == "sort"
        if "gather" in name or "scatter" in name:
            sizes = {int(np.prod(v.aval.shape))
                     for v in (*eqn.invars, *eqn.outvars)
                     if hasattr(v.aval, "shape")}
            moves.append((name, sizes & over))
    assert sorts == 2, sorts          # the plan's, and dweights' way back
    assert moves, "the pools' row gathers and scatter-adds are there"
    assert not any(hit for _, hit in moves), moves


def test_the_timing_tools_plan_pieces_agree_off_the_chip(monkeypatch, capsys):
    """`tools/held_experts_timing.py --pieces`' plan pieces at a small
    size: off a TPU no time is printed, but each pair's outputs (the one
    sort against argsort + gather, the sort back against the scatter) are
    still compared bit for bit, and the tool's exit code hangs on it."""
    import json
    import types
    monkeypatch.syspath_prepend(os.path.join(ROOT, "tools"))
    import held_experts_timing as tool
    monkeypatch.setattr(tool, "PLAN_SIZES", ((64, 8, 4, 32), (48, 22, 8, 64)))
    assert tool.plan_pieces(types.SimpleNamespace(reps=1),
                            np.random.default_rng(0))
    lines = [json.loads(line) for line in capsys.readouterr().out.split("\n")
             if line.startswith("{")]
    assert [(line["piece"], line["assignments"]) for line in lines] == [
        ("plan_sort", 512), ("dweights_back", 512),
        ("plan_sort", 1056), ("dweights_back", 1056)]
    assert all(line["unequal"] == 0 and 0 < line["arrived"] < line[
        "assignments"] for line in lines)
    assert not any(key.endswith("_ms") for line in lines for key in line)
