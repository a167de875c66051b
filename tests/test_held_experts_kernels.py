"""The held experts' row scatter-add (`ops/moe_rows_pallas.py`) against its
jnp form, in interpret mode on the CPU: tokens that sit several times in
one tile across run boundaries, rows of the cells' widths, and
`held_experts` through the door (`ops/dispatch.py`) with the kernel
against without it.  Interpret mode completes a copy at its
start, so it holds the arithmetic, the tails' masks and the segment walk,
not the overlap of copies in flight: the chip does (`chip_smoke.py`,
`tools/held_experts_timing.py --pieces`).  The layer with every place past
the runs poisoned (nothing there is masked at `[places, width]`) against
a dense oracle.  The plan's sorts are in `test_held_experts_plan.py`."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from geomx_tpu.ops import dispatch, held_experts as layer
from geomx_tpu.ops import moe_rows_pallas as rows_ops
from geomx_tpu.ops.held_experts import held_experts

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


@pytest.mark.parametrize("pool", [256, 32],
                         ids=["first_pool_only", "later_pools"])
@pytest.mark.parametrize("d", [2048, 2304],
                         ids=["2048_the_kernel", "2304_xlas_scatter_add"])
@pytest.mark.parametrize("gated", [True, False], ids=["gated", "ungated"])
def test_places_past_the_runs_are_never_read(gated, d, pool, monkeypatch):
    """Every place past the runs of every grouped product's result is set
    to NaN (a pool's body masks and scales nothing at `[places, width]`:
    the products and the scatter-add leave those rows alone): y and all
    five gradients are finite and the dense oracle's, which weighs the
    hidden layer before the second product as the layer does.  Both
    expert forms, both ways back (2,048: the row kernel interpreted;
    2,304: XLA's scatter-add), a first pool that holds everything and one
    that overflows into later pools whose last is part empty."""
    rng = np.random.default_rng(d + pool)
    tokens, width, held, offset, top_k, router, rows = 96, 64, 4, 3, 4, 12, 16
    x = jnp.asarray(rng.standard_normal((tokens, d)), jnp.float32)
    idx = routing(rng, tokens, top_k, router)
    w = jnp.asarray(rng.uniform(0.1, 0.5, (tokens, top_k)), jnp.float32)
    mats = [jnp.asarray(rng.standard_normal(shape) * 0.1, jnp.float32)
            for shape in ((held, d, width), (held, d, width),
                          (held, width, d))]
    if not gated:
        mats[0] = None
    r = jnp.asarray(rng.standard_normal((tokens, d)), jnp.float32)
    product = layer._gmm

    def poisoned(lhs, rhs, sizes, *rest):
        out = product(lhs, rhs, sizes, *rest)
        past = jnp.arange(out.shape[0]) >= jnp.sum(sizes)
        return jnp.where(past[:, None], jnp.nan, out)

    def ours(x_, w_, *m):
        y, counts, dropped = held_experts(x_, idx, w_, *m, offset, rows,
                                          None, pool)
        return jnp.sum(y * r), (y, counts, dropped)

    def dense(x_, w_, gate, up, down):
        y = jnp.zeros_like(x_)
        for e in range(held):
            weight = jnp.sum(jnp.where(idx == offset + e, w_, 0.0), axis=-1)
            h = x_ @ up[e]
            h = (jnp.square(jax.nn.relu(h)) if gate is None
                 else jax.nn.silu(x_ @ gate[e]) * h)
            y = y + (weight[:, None] * h) @ down[e]
        return jnp.sum(y * r), y

    argnums = tuple(i for i, m in enumerate((x, w, *mats)) if m is not None)
    monkeypatch.setattr(layer, "_gmm", poisoned)
    with dispatch.kernels("interpret"):
        (_, (y, counts, dropped)), got = jax.jit(jax.value_and_grad(
            ours, argnums=argnums, has_aux=True))(x, w, *mats)
    (_, want_y), want = jax.jit(jax.value_and_grad(
        dense, argnums=argnums, has_aux=True))(x, w, *mats)
    arrived = int(jnp.sum(counts))
    assert int(dropped) == 0 and rows_ops.slabs_are_whole(d) == (d == 2048)
    # places past the runs: in the first pool, or in the last later one
    assert (pool - arrived if pool == 256
            else -(arrived - pool) % (2 * rows)) > 0
    for name, ours_, theirs in zip(
            ("y", "dx", "dweights", *(["dgate"] * gated), "dup", "ddown"),
            (y, *got), (want_y, *want)):
        assert bool(jnp.all(jnp.isfinite(ours_))), name
        theirs = np.asarray(theirs, np.float32)
        np.testing.assert_allclose(
            np.asarray(ours_, np.float32), theirs, err_msg=name,
            atol=3e-5 * max(1.0, float(np.max(np.abs(theirs)))))
