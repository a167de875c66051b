"""The held experts' plan (`ops/held_experts._plan`): token and weight ride
its one sort and `dweights` comes back by a sort, bit for bit what the
argsort, gather and scatter they replaced gave (PR 39), nothing in the
layer's jaxpr gathers or scatters over the routed assignments, and nothing
in its lowered program selects or multiplies at `[places, d]`.  The row
kernel and the layer through the door are in
`test_held_experts_kernels.py`: two files, so that two workers share the
compiles."""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_checks as checks
from geomx_tpu.ops import dispatch
from geomx_tpu.ops.held_experts import held_experts

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

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
    idx = _routing_with(rng, tokens, top_k, held, router, "some")[0]
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
    for eqn in checks.equations(jaxpr.jaxpr):
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


@pytest.mark.parametrize("d", [2048, 2304])
@pytest.mark.parametrize("gated", [True, False], ids=["gated", "ungated"])
def test_no_select_or_multiply_over_a_pools_rows(gated, d):
    """The layer's forward and backward, lowered for a TPU (the grouped
    products and, at 2,048, the row kernel are custom calls), hold no
    `select` and no `multiply` whose result is `[places, d]`, for the
    first pool's places or a later pool's: a pool's body masks and scales
    at the hidden width and at `[places]` only, and a gather that cannot
    miss has nothing to fill.  (The parent's had five `where` passes, a
    `dys * weight` and three fill selects a pool.)"""
    rng = np.random.default_rng(7)
    tokens, width, held, top_k, router, rows, pool = 40, 128, 4, 4, 12, 8, 64
    idx = _routing_with(rng, tokens, top_k, held, router, "some")[0]
    w = jnp.asarray(rng.uniform(0.1, 0.5, (tokens, top_k)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((tokens, d)), jnp.bfloat16)
    mats = [jnp.zeros(shape, jnp.float32) for shape in (
        (held, d, width), (held, d, width), (held, width, d))]
    if not gated:
        mats[0] = None

    def loss(x_, w_, *m):
        return jnp.sum(held_experts(x_, idx, w_, *m, 0, rows, None, pool)[0])

    argnums = tuple(i for i, m in enumerate((x, w, *mats)) if m is not None)
    with dispatch.kernels("native"):
        text = jax.jit(jax.value_and_grad(loss, argnums=argnums)).trace(
            x, w, *mats).lower(lowering_platforms=("tpu",)).as_text()
    wide = re.compile(rf"tensor<({pool}|{2 * rows})x{d}x(f32|bf16)>$")
    ops = [line.strip() for line in text.splitlines()
           if wide.search(line.rstrip())]
    assert len(ops) >= 16, "a pool's rows are there: gathers, products"
    assert sum("tpu_custom_call" in op for op in ops) >= 4
    passes = [op for op in ops
              if re.search(r"stablehlo\.(select|multiply)\b", op)]
    assert not passes, passes


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


def test_the_timing_tools_scatter_by_places_runs_off_the_chip(monkeypatch,
                                                              capsys):
    """`tools/held_experts_timing.py --pieces`' scatter-add by places at a
    small size: off a TPU every size and load's program runs, and neither
    a time nor the line through the sizes is printed."""
    import json
    import types
    monkeypatch.syspath_prepend(os.path.join(ROOT, "tools"))
    import held_experts_timing as tool
    monkeypatch.setattr(tool, "SCATTER_PLACES", (16, 32, 64))
    monkeypatch.setattr(tool, "SCATTER_WIDTH", 256)
    tool.scatter_by_places(types.SimpleNamespace(reps=1), 24,
                           np.random.default_rng(0))
    out = capsys.readouterr().out
    lines = [json.loads(line) for line in out.split("\n")
             if line.startswith("{")]
    assert [(line["places"], line["load"], line["real"]) for line in lines
            ] == [(16, "none", 0), (16, "half", 8), (32, "none", 0),
                  (32, "half", 16), (64, "none", 0), (64, "half", 32)]
    assert "Line" not in out
    assert not any(key.endswith("_ms") for line in lines for key in line)
