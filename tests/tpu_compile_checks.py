"""What the `test_tpu_compile_*.py` files share, not a test file itself:
shapes, a program compiled for the described chip (`chip` of
``conftest.py``), its Pallas calls by name, and the held experts' cases,
whose eight compiles are two files' work (forward, backward)."""
import re

import jax
import jax.numpy as jnp


def f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def compiled_text(chip, fn, shapes):
    """`fn` compiled by libtpu for the described chip, as text."""
    args = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def directed(run, direction, argnums=(0, 1, 2, 3, 4)):
    """`run` itself forward; backward, the gradient of the sum of its
    (first) output."""
    if direction == "forward":
        return run

    def total(*a):
        out = run(*a)
        return jnp.sum(out[0] if isinstance(out, tuple) else out)
    return jax.grad(total, argnums=argnums)


def mixer_step_text(chip, mixer, length, hidden):
    """A mixer's forward and gradient (parameters and input, bf16) as one
    program under the native kernels: the parameters' shapes, the text."""
    from geomx_tpu.ops import dispatch
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
    return params, text


def kernel_calls(text):
    """The compiled program's Pallas calls by instruction name, in order."""
    return re.findall(r"%([\w.-]+) = [^\n]*custom_call_target="
                      r'"tpu_custom_call"', text)


# (tokens, hidden, held, width, tile, first pool): the decoder cells'
# SwiGLU expert layers, 8 picks a token (131,072 assignments)
HELD_EXPERTS = {
    "kimi-8-of-256": (16384, 2304, 8, 1024, 512, None),
    "trinity-16-of-128": (16384, 2048, 16, 1024, 512, 32768),
    "mellum-16-of-64": (16384, 2304, 16, 896, 512, 65536),
}
# the third decoder cell's: 16,384 tokens of the 1,024-wide latent, 22 of
# 512 experts a token, 8 held of width 2,688, un-gated squared ReLU (no
# gate kernel), a first pool of twice what even routing sends the chip
UNGATED_EXPERTS = (16384, 1024, 8, 2688, 512, 11264)


def held_experts_accepted(chip, sizes, direction, top_k=8, gated=True):
    """The held experts' walk at a decoder cell's sizes, bf16 operands:
    the grouped-product kernels' tiles (K = 1,024 / N = 2,688 un-gated)
    have to fit VMEM, forward and backward.  Where a row is whole slabs
    (hidden 2,048, the latent's 1,024) the pools' rows go back through the
    kernel `moe_row_scatter_add` (ops/moe_rows_pallas.py: y forward, dx
    backward), which `moe_dispatch_ms` finds by its scope, and no XLA
    scatter of rows is left (the gathers are XLA's: they cost what their
    bytes cost); at 2,304 the door keeps XLA's scatter-add."""
    from geomx_tpu.ops import dispatch
    from geomx_tpu.ops.held_experts import held_experts
    tokens, d, held, f, rows, pool = sizes
    on = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                   sharding=chip)
    mats = [on((held, d, f), jnp.float32)] * (1 + gated) + [
        on((held, f, d), jnp.float32)]
    run = lambda x, idx, w, *mats: held_experts(
        x, idx, w, *(mats if gated else (None, *mats)), 0, rows, False, pool)
    with dispatch.kernels("native"):
        text = jax.jit(directed(
            run, direction, (0, *range(2, 3 + len(mats))))).lower(
            on((tokens, d), jnp.bfloat16), on((tokens, top_k), jnp.int32),
            on((tokens, top_k), jnp.float32), *mats).compile().as_text()
    calls = kernel_calls(text)
    moves = [c.split(".")[0] for c in calls if c.startswith("moe_row")]
    assert any(c.startswith("gmm") for c in calls), calls
    assert any(c.startswith("tgmm") for c in calls) == (
        direction == "backward"), calls
    xla_scatters = re.search(r"= f32\[\d+,%d\]\S* scatter\(" % d, text)
    if d % 1024 == 0:
        # the first pool and the `while`'s later pools, each once
        assert moves == ["moe_row_scatter_add"] * 2, calls
        assert not xla_scatters
    else:
        # slabs of 2,304 would pad: the door keeps XLA's scatter-add
        assert moves == [] and xla_scatters, calls


def row_kernel_carries_its_name(chip, cell):
    """The scatter-add kernel alone at a cell's first pool: one custom
    call, by its name, at the tile its VMEM budget gives."""
    from geomx_tpu.ops import moe_rows_pallas as rows_ops
    tokens, d, held, _, rows, pool = HELD_EXPERTS[cell]
    places = pool or 2 * held * rows
    on = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    text = jax.jit(rows_ops.moe_row_scatter_add).lower(
        on((tokens, d), jnp.float32), on((places, d), jnp.float32),
        on((places,), jnp.int32), on((held,), jnp.int32)).compile().as_text()
    calls = kernel_calls(text)
    assert [c.split(".")[0] for c in calls] == ["moe_row_scatter_add"], calls
    assert rows_ops.tile_rows(places, d) == (512 if d == 2048 else 256)
