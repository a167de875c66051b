"""The `kimi_linear` family and its cell: the configuration file against
the catalog row it was cut from, the whole tiny decoder through
`Trainer.fit` against `reference_steps` under the harness, the float8
control, the FLOP and byte functions, and the new per-layer readers on a
program that lacks what they read."""
import json
import os

import numpy as np
import pytest

from bench_paths import ROOT, TINY

from benchmark import check, run
from benchmark.cells import Registry

KIMI = os.path.join(ROOT, "tests", "benchmark", "data_kimi")

# `config` of the catalog's row `Kimi-Linear-48B-A3B-Instruct`
# (model-configs guide, architectures.jsonl), copied whole
CATALOG = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840}
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]


def registry():
    return Registry(ROOT, extra=[KIMI, TINY])


def real_cell():
    return Registry(ROOT).cell("kimilinear-fsa-1c")


@pytest.mark.parametrize("key", sorted(CATALOG))
def test_configuration_keeps_every_published_key(key):
    config = real_cell()["config"]
    if key in REDUCED:
        assert config[key] != CATALOG[key]
        assert config["published"][key] == CATALOG[key]
    else:
        assert config[key] == CATALOG[key], key


def test_configuration_states_its_cut():
    config = real_cell()["config"]
    assert config["reduced"] == REDUCED
    assert config["num_experts"] == 8 and config["router_experts"] == 256
    assert config["vocab_size"] * 8 == CATALOG["vocab_size"]
    assert config["kept_layers"] == [1, 2, 3, 4, 5]
    assert "32 chips" in config["deployment"]
    family = real_cell()["family"]
    assert family.layer_kinds(config) == (
        ("kda", "mlp"), ("kda", "moe"), ("kda", "moe"), ("mla", "moe"),
        ("kda", "moe"))
    path = os.path.join("/opt/skills/guides/model-configs",
                        "architectures.jsonl")
    if os.path.exists(path):        # the literal above is the row's own
        with open(path) as f:
            rows = [json.loads(line) for line in f]
        row = [r for r in rows if r["name"] == "Kimi-Linear-48B-A3B-Instruct"]
        assert row[0]["config"] == CATALOG
        assert row[0]["source_url"] == config["source"]


def test_parameter_count_is_the_files_and_the_issues():
    import jax
    cell = real_cell()
    config = cell["config"]
    model = cell["family"].build_model(config)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           np.zeros((1, 64), np.int32)))["params"]
    count = lambda tree: sum(int(np.prod(s.shape))
                             for s in jax.tree.leaves(tree))
    stated = config["parameters"]
    assert count(shapes) == stated["total"] == 602_433_408
    assert count(shapes["layer1"]["mixer"]["core"]) == stated["kda_mixer"]
    assert count(shapes["layer4"]["mixer"]["core"]) == stated["mla_mixer"]
    assert count(shapes["layer1"]["ffn"]["core"]) == stated["dense_mlp"]
    experts = count({k: v for k, v in shapes["layer2"]["ffn"]["core"].items()
                     if k.startswith("experts_")})
    assert experts == 8 * stated["routed_expert"]
    assert (count(shapes["layer2"]["ffn"]["core"]) - experts
            == stated["moe_outside_routed"])
    # every RMSNorm weight is a leaf named `scale` (weights.py makes ones)
    names = [p[-1].key for p, _ in
             jax.tree_util.tree_flatten_with_path(shapes)[0]]
    assert names.count("scale") == 2 * 5 + 4 + 1 + 1


def test_flops_and_bytes_from_shapes():
    cell = real_cell()
    family, config = cell["family"], cell["config"]
    per_token = family.forward_flops_per_token(config)
    assert per_token == pytest.approx(0.770e9, rel=0.01)
    assert family.train_flops_per_sample(config) == 3 * 8192 * per_token
    scan = family.kda_scan_shape(config)
    assert scan == {"tokens": 16384, "heads": 32, "key_dim": 128,
                    "value_dim": 128, "layers": 4}
    assert family.kda_scan_flops_per_step(scan) == 21 * 128 * 128 * 16384 * 32 * 4
    assert family.kda_scan_bytes_per_step(scan) == 4352 * 16384 * 32 * 4
    latent = family.latent_attention_shape(config)
    assert family.latent_attention_flops_per_step(latent) == (
        960 * 2 * 32 * 8192 ** 2)
    # the flash kernel's own roofline reader must not apply to this family
    assert not hasattr(family, "attention_shape")


def test_data_is_tokens_of_the_slice_with_the_next_token_as_label():
    cell = real_cell()
    x, y = cell["family"].make_data(cell["config"],
                                    np.random.default_rng(2 ** 31 + 5), 3)
    assert x.shape == y.shape == (3, 8192) and x.dtype == np.int32
    assert 0 <= x.min() and x.max() < 20480
    assert np.array_equal(x[:, 1:], y[:, :-1])


def rehearse(name, seed):
    return run.run_cell(registry(), name, seed, 30.0, False,
                        rehearse_segments=3)


def test_the_whole_tiny_decoder_through_fit_meets_the_reference(capsys):
    """float32 program: `Trainer.fit` (loader, the model's own loss, FSA's
    dense tier, Adam) against `reference_steps` on the plain reference, to
    rounding, over three steps."""
    result = rehearse("tiny-kimi-f32", 2 ** 31 + 77)
    out = capsys.readouterr().out
    assert result["correct"] is True, out
    checks = result["checks"]
    assert checks["first_grad_error"]["value"] < 1e-4
    assert checks["loss_gap"]["value"] < 1e-5
    assert checks["delta_gap"]["value"] < 1e-3
    # the expert layer's counters came through the window's LoopStats
    stats = json.loads([line for line in out.splitlines()
                        if line.startswith("LOOP_STATS ")][0][11:])
    assert stats["counters"]["moe/dropped"]["total"] == 0.0
    assert stats["counters"]["moe/assignments_mean"]["count"] == 3


def test_the_bfloat16_program_is_sound_and_the_float8_control_is_not():
    """The tiny cell at bfloat16: limits can sit between the program's
    readings and the control's (the plain reference at float8 in the
    program's place), as the chip cell's do at its own size."""
    from benchmark.references.numerics import next_lower
    cell = registry().cell("tiny-kimi-f32")
    cell["config"]["precision"] = "bfloat16"
    config, traffic = cell["config"], cell["traffic"]
    seed = 2 ** 31 + 123
    trainer = run.build_trainer(cell)
    rows = config["per_chip_batch"] * traffic["n_check"]
    x, y = cell["family"].make_data(config, np.random.default_rng(seed), rows)
    state, shapes = run.initial_state(cell, trainer, seed, x[:2])
    _, program = run.first_steps(cell, trainer, state, shapes, x, y, seed)
    reference = run.run_reference(cell, shapes, x, y, seed)
    lower = run.run_reference(cell, shapes, x, y, seed,
                              next_lower(config["precision"]))
    limits = {name: {"limit": limit} for name, limit in [
        ("loss_gap", 0.03), ("first_grad_gap", 0.2), ("delta_gap", 0.3),
        ("first_grad_error", 0.2)]}
    sound = check.compare(program, reference, 0)
    control = check.compare(lower, reference, 0)
    assert check.verdict(sound, limits)[0] is True, sound
    assert check.verdict(control, limits)[0] is False, control
    assert control["first_grad_error"] > 2 * sound["first_grad_error"]


@pytest.mark.parametrize("name", ["kda_scan_ms", "kda_scan_roofline_pct",
                                  "latent_attn_roofline_pct",
                                  "moe_experts_ms", "lm_loss_ms",
                                  "moe_dropped_pct"])
def test_new_readers_apply_to_the_decoder_only_and_read_nothing_from_nothing(
        name):
    reg = Registry(ROOT)
    reader = {m.NAME: m for m in reg.layer_metrics()}[name]
    assert reader.applies(reg.cell("kimilinear-fsa-1c"))
    assert not reader.applies(reg.cell("bertlarge-fsa-1c"))
    assert not reader.applies(reg.cell("resnet18-bsc-1c"))
    # no trace, no table, no counters (the parent's program): None, no raise
    ctx = {"cell": reg.cell("kimilinear-fsa-1c"), "trace": None,
           "loop_stats": {"steps": 4, "wall_s": 1.0, "phases": {}},
           "step_layers": None, "peaks": {"bf16_flops_per_s": 197e12,
                                          "hbm_bytes_per_s": 819e9}}
    assert reader.read(ctx) is None


def test_scope_readers_join_the_trace_with_the_programs_table():
    from geomx_tpu.telemetry.layers import OpLayer
    reg = Registry(ROOT)
    readers = {m.NAME: m for m in reg.layer_metrics()}
    fb = "step/forward_backward/"
    table = {
        "fusion.1": OpLayer(fb + "kda/scan", "kernels", "forward"),
        "fusion.2": OpLayer(fb + "kda/scan", "kernels", "backward"),
        "custom.3": OpLayer(fb + "mla/attention", "kernels", "backward"),
        "fusion.4": OpLayer(fb + "moe/experts", "step program", "forward"),
        "fusion.5": OpLayer(fb + "lm/loss", "step program", "forward"),
        "fusion.6": OpLayer(fb + "kda/proj", "step program", "forward")}
    ctx = {"cell": reg.cell("kimilinear-fsa-1c"), "step_layers": table,
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "trace": {"steps": 2, "by_op_s": {
               "fusion.1": 0.10, "fusion.2": 0.30, "custom.3": 1.0,
               "fusion.4": 0.02, "fusion.5": 0.04, "fusion.6": 0.5,
               "not.in.table": 9.0}}}
    assert readers["kda_scan_ms"].read(ctx) == pytest.approx(200.0)
    assert readers["moe_experts_ms"].read(ctx) == pytest.approx(10.0)
    assert readers["lm_loss_ms"].read(ctx) == pytest.approx(20.0)
    # bytes bind: 4,352 B x 16,384 x 32 x 4 layers / 819 GB/s = 11.14 ms
    assert readers["kda_scan_roofline_pct"].read(ctx) == pytest.approx(
        100 * 11.1434 / 200.0, rel=1e-3)
    # 960 B H L^2 = 4.123 TFLOP / 197 TFLOP/s = 20.93 ms of 500 ms
    assert readers["latent_attn_roofline_pct"].read(ctx) == pytest.approx(
        100 * 20.929 / 500.0, rel=1e-3)
    ctx["loop_stats"] = {"steps": 8, "counters": {
        "moe/dropped": {"count": 4, "total": 0.0, "last": 0.0, "max": 0.0},
        "moe/assignments_mean": {"count": 4, "total": 2048.0, "last": 512.0,
                                 "max": 512.0}}}
    assert readers["moe_dropped_pct"].read(ctx) == 0.0
    ctx["loop_stats"]["counters"]["moe/dropped"]["total"] = 655.36
    assert readers["moe_dropped_pct"].read(ctx) == pytest.approx(1.0)


def test_every_program_key_of_the_file_reaches_the_model():
    """`program` records what was chosen to make the cell fit; a key that
    `build_model` did not read would drift from the code in silence."""
    cell = real_cell()
    config = json.loads(json.dumps(cell["config"]))
    model = cell["family"].build_model(config).cfg
    program = config["program"]
    assert (model.kda_chunk, model.kda_sub, model.loss_block,
            model.expert_rows, model.remat) == (
        program["kda_chunk"], program["kda_sub_block"],
        program["loss_block_tokens"], program["expert_block_rows"],
        program["remat_each_layer"]) == (64, 16, 2048, 512, True)
    assert set(program) == {"kda_chunk", "kda_sub_block", "loss_block_tokens",
                            "expert_block_rows", "remat_each_layer", "note"}
    config["program"] = dict(program, kda_chunk=32, kda_sub_block=8,
                             loss_block_tokens=512, expert_block_rows=128,
                             remat_each_layer=False)
    other = cell["family"].build_model(config).cfg
    assert (other.kda_chunk, other.kda_sub, other.loss_block,
            other.expert_rows, other.remat) == (32, 8, 512, 128, False)


def test_the_cell_trains_at_the_rate_the_issue_names():
    """ISSUE 27 names Adam at 1e-4, as the other sequence model's cells
    run; the seeded router is a matrix like the others."""
    reg = Registry(ROOT)
    rates = {name: reg.cell(name)["config"]["optimizer"]
             for name in ("kimilinear-fsa-1c", "bertlarge-fsa-1c",
                          "bertlarge-bsc-1c")}
    assert all(r == {"name": "adam", "lr": 1e-4, "b1": 0.9, "b2": 0.999,
                     "eps": 1e-8} for r in rates.values()), rates
    family = real_cell()["family"]
    assert family.weight_std(("layer2", "ffn", "core", "router_kernel"),
                             (2304, 256)) == pytest.approx(2304 ** -0.5)


def test_unscoped_ops_tool_lists_what_the_table_leaves_unnamed():
    import importlib.util
    from geomx_tpu.telemetry.layers import UNNAMED, UNSCOPED, OpLayer
    spec = importlib.util.spec_from_file_location(
        "unscoped_ops", os.path.join(ROOT, "benchmark", "tools",
                                     "unscoped_ops.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    hlo = """HloModule jit_s

%body.3 (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]{0}) parameter(0)
  ROOT %copy.4 = (s32[], f32[4]{0}) copy(%p)
}

ENTRY %main (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %copy-start.2 = (f32[4]{0:S(1)}, f32[4]{0}, u32[]) copy-start(%a)
  %fusion.7 = f32[4]{0} fusion(%a), kind=kLoop, calls=%f, metadata={op_name="jit(s)/add"}
  ROOT %fusion.8 = f32[4]{0} fusion(%a), kind=kLoop, calls=%g, metadata={op_name="jit(s)/kda/scan/mul"}
}
"""
    where = tool.instructions_of(hlo)
    assert where["copy.4"] == ("copy", "(s32[], f32[4]{0})", "body.3")
    assert where["copy-start.2"][0::2] == ("copy-start", "ENTRY")
    table = {"copy.4": UNNAMED, "copy-start.2": UNNAMED, "fusion.7": UNSCOPED,
             "fusion.8": OpLayer("kda/scan", "kernels", None)}
    rows = tool.unscoped_rows(
        {"copy.4": 0.3, "copy-start.2": 0.1, "fusion.7": 0.2, "fusion.8": 9.0,
         "late.1": 0.05}, table, where)
    assert [(r[1], r[2]) for r in rows] == [
        ("copy.4", "unnamed"), ("fusion.7", "named"),
        ("copy-start.2", "unnamed"), ("late.1", "unknown")]
    assert rows[0][3:] == ("copy", "(s32[], f32[4]{0})", "body.3")
