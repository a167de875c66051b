"""Ask the TPU's compiler, without a TPU (see
``test_tpu_compile_engine.py``): the decoders' kernels and scans (KDA,
SSD, the grouped-query mixer's streamed pass) at the decoder cells'
shapes.  The held experts' are in ``test_tpu_compile_experts_forward.py``
and ``test_tpu_compile_experts_backward.py``."""

import functools

import jax
import jax.numpy as jnp
import pytest

import tpu_compile_checks as checks


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
    compiled = jax.jit(checks.directed(run, direction)).lower(*args).compile()
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
    with dispatch.kernels("native"):
        text = jax.jit(checks.directed(run, direction)).lower(
            *args).compile().as_text()
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
    with dispatch.kernels("native"):
        compiled = jax.jit(checks.directed(run, direction)).lower(
            *args).compile()
    assert " while(" not in compiled.as_text()
    # a sequence's decay matrices and their cotangents, not gigabytes
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


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
    from geomx_tpu.ops import gqa_elementwise as ge
    length, hidden, heads, kv_heads, d = 8192, 2048, 32, 4, 128
    mixer = GQAMixer(heads, kv_heads, d, 2048 if kind == "window" else None,
                     10000.0 if kind == "window" else None, 1e-5,
                     jnp.bfloat16)
    _, text = checks.mixer_step_text(chip, mixer, length, hidden)
    calls = [c.split(".")[0] for c in checks.kernel_calls(text)]
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
    YaRN's is no other kernel; either kind's attention backward is the
    one kernel."""
    from geomx_tpu.models.mellum import MellumConfig
    from geomx_tpu.ops import gqa_elementwise as ge
    length, hidden = 16384, 2304
    cfg = MellumConfig(
        vocab=24576, hidden=hidden, layers=(), num_heads=32, num_kv_heads=4,
        head_dim=128, window=1024, rope_theta=500000.0, expert_width=896,
        num_experts=64, experts_held=16, expert_offset=0, top_k=8,
        yarn=ge.Yarn(500000.0, 16.0, 8192, 32.0, 1.0, 1.2772588722239782))
    mixer = cfg.make_mixer(kind, jnp.bfloat16)
    assert isinstance(mixer.rope, ge.Yarn) == (kind == "global")
    params, text = checks.mixer_step_text(chip, mixer, length, hidden)
    assert "gate_kernel" not in params
    calls = [c.split(".")[0] for c in checks.kernel_calls(text)]
    # the backward is ONE kernel that keeps the group of eight's dq^T
    # (64 MiB of the core's 128) beside this program's other kernels
    for name, count in (("gqa_norm_rotary_fwd", 1),
                        ("gqa_norm_rotary_bwd", 1),
                        ("flash_attention_fwd", 1),
                        ("flash_attention_bwd", 1),
                        ("flash_attention_bwd_dq", 0),
                        ("flash_attention_bwd_dkv", 0)):
        assert calls.count(name) == count, calls
    for backward in (False, True):
        plan = ge.norm_rotary_plan((1, length, 32, 128), (1, length, 4, 128),
                                   jnp.bfloat16, backward)
        assert plan.tile >= 128 and plan.vmem_bytes <= ge.VMEM_BUDGET



def test_v5e_compiler_accepts_the_fifth_decoders_step(chip):
    """`glm-4.7-flash-ep8` as its family builds it, at the cell's sizes
    (one sequence of 16,384 tokens, bf16; 706.5 M parameters): the loss
    the model brings (next token + 0.3 x second-next through the module)
    and its gradient as one program under the native kernels.  Six latent
    layers (five blocks and the module's) call the flash kernels at 20
    heads of 256/256, the forward and the ONE backward kernel (16 MiB of
    dq^T in VMEM) once each, no dq or dk/dv kernel; the five expert
    layers' pools go back through the row kernel (hidden 2,048 is whole
    slabs); and this program's temporaries (one sequence's mixer
    internals, the dense layer's and a loss block's, 6.03 GB as PR 45
    compiled it) leave the fp32 weights and Adam's two moments (three
    times the arguments, 8.48 GB) their place under the chip's 16.91 GB;
    the trainer's own step, which hands each gradient leaf to Adam as it
    comes, compiled to 8.48 + 8.02 GB."""
    import collections
    import json
    import os
    from benchmark.cells import Registry
    from geomx_tpu.ops import dispatch
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cell = Registry(root).cell("glm47flash-fsa-1c")
    config = cell["config"]
    model = cell["family"].build_model(config)
    on = lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                           sharding=chip)
    x = on(jax.ShapeDtypeStruct((1, config["sequence_length"]), jnp.int32))
    params = jax.tree.map(on, jax.eval_shape(
        model.init, jax.random.PRNGKey(0), x)["params"])
    count = sum(leaf.size for leaf in jax.tree.leaves(params))
    assert count == config["parameters"]["total"] == 706_518_528
    step = jax.value_and_grad(lambda p, x_, y_: model.apply(
        {"params": p}, x_, y_, method="loss_and_aux"), has_aux=True)
    with dispatch.kernels("native"):
        compiled = jax.jit(step).lower(params, x, x).compile()
    calls = collections.Counter(
        c.split(".")[0] for c in checks.kernel_calls(compiled.as_text()))
    for name, count_ in (("flash_attention_fwd", 6),
                         ("flash_attention_bwd", 6),
                         ("flash_attention_bwd_dq", 0),
                         ("flash_attention_bwd_dkv", 0)):
        assert calls[name] == count_, calls
    assert calls["gmm"] and calls["tgmm"] and calls["moe_row_scatter_add"]
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes == pytest.approx(4 * count, rel=1e-3)
    assert memory.temp_size_in_bytes < 6.5e9, json.dumps(
        {"temp": memory.temp_size_in_bytes})
    assert (3 * memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 16.91e9)


@functools.lru_cache(maxsize=None)
def looped_step(chip):
    """`ouro-2.6b-6of48` as its family builds it, at the cell's sizes: the
    loss the model brings and its gradient as one program under the
    native kernels, compiled once for the cases below: (the number of
    parameters, the compiled program)."""
    import os
    from benchmark.cells import Registry
    from geomx_tpu.ops import dispatch
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cell = Registry(root).cell("ouro26b-fsa-1c")
    config = cell["config"]
    model = cell["family"].build_model(config)
    on = lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                           sharding=chip)
    x = on(jax.ShapeDtypeStruct((1, config["sequence_length"]), jnp.int32))
    params = jax.tree.map(on, jax.eval_shape(
        model.init, jax.random.PRNGKey(0), x)["params"])
    count = sum(leaf.size for leaf in jax.tree.leaves(params))
    assert count == config["parameters"]["total"] == 509_661_185
    step = jax.value_and_grad(lambda p, x_, y_: model.apply(
        {"params": p}, x_, y_, method="loss_and_aux"), has_aux=True)
    with dispatch.kernels("native"):
        return count, jax.jit(step).lower(params, x, x).compile()


def test_v5e_compiler_accepts_the_looped_decoders_step(chip):
    """`ouro-2.6b-6of48` as its family builds it, at the cell's sizes (one
    sequence of 8,192 tokens, bf16; 509.7 M parameters, four loop steps):
    the expected-exit loss the model brings and its gradient as one
    program under the native kernels.  The four passes stand in the
    program one after the other: the flash kernels at 16 equal heads of
    128 are called 24 times forward and 24 times backward (the ONE
    backward kernel); the 24 rematerialised forward calls are not there,
    XLA shares each with the pass's own (as one scan over the loop steps
    the program holds 12 + 6 calls and runs 48 + 24); and this program's
    temporaries (the 24 applications' saved halves, the four normed
    streams, a loss block with its gradient's two products, 7.72 GB as
    PRs 48 and 49 compiled it) leave the fp32 weights and Adam's two
    moments (three times the arguments, 6.12 GB) their place under the
    chip's 16.91 GB."""
    import collections
    import json
    count, compiled = looped_step(chip)
    calls = collections.Counter(
        c.split(".")[0] for c in checks.kernel_calls(compiled.as_text()))
    assert dict(calls) == {"flash_attention_fwd": 24,
                           "flash_attention_bwd": 24}, calls
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes == pytest.approx(4 * count, rel=1e-3)
    assert memory.temp_size_in_bytes < 8.2e9, json.dumps(
        {"temp": memory.temp_size_in_bytes})
    assert (3 * memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 16.91e9)


def test_the_looped_decoders_loss_makes_its_gradient_going_forward(chip):
    """Under `lm/loss` the same program holds ONE scan over the 16 blocks
    of 2,048 rows (4 steps x 8,192 tokens) with three products in its
    body: the block's float32 logits [2048, 49152], dhead's [2048, 49152]
    (hidden 2,048 is a block's rows too) and dh's [2048, 2048], all three
    in the forward half of the scope.  The backward half (JAX's
    `transpose(`) holds no product and no `while`: it scales what the
    forward made, so no second pass makes a block's logits."""
    import re
    text = looped_step(chip)[1].as_text()
    under = r'[^\n]*op_name="([^"]*lm/loss[^"]*)"'
    products = re.findall(
        r"= (\w+)\[([\d,]*)\]\S* convolution\(" + under, text)
    assert sorted((dtype, shape) for dtype, shape, _ in products) == [
        ("f32", "2048,2048"), ("f32", "2048,49152"), ("f32", "2048,49152")]
    loops = re.findall(r" while\(" + under, text)
    assert len(loops) == 1, loops
    assert not [name for *_, name in products if "transpose(" in name]
    assert "transpose(" not in loops[0]
