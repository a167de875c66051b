"""The `afmoe` family and its cell: the configuration file against the
catalog row it was cut from, the parameter table, the FLOP and pair counts,
the whole tiny decoder through `Trainer.fit` against `reference_steps`
under the harness, the float8 control, the new per-layer readers on a
program that lacks what they read, and the `program` keys."""
import json
import os

import numpy as np
import pytest

from bench_paths import ROOT, TINY

from benchmark import check, run
from benchmark.cells import Registry

AFMOE = os.path.join(ROOT, "tests", "benchmark", "data_afmoe")

WINDOW, FULL = "sliding_attention", "full_attention"
# `config` of the catalog's row `Trinity-Mini` (model-configs guide,
# architectures.jsonl), copied whole
CATALOG = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144,
    "layer_types": [WINDOW, WINDOW, WINDOW, FULL] * 8,
    "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
    "model_type": "afmoe", "moe_intermediate_size": 1024,
    "mup_enabled": True, "n_group": 1, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_expert_groups": 1, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 32,
    "num_key_value_heads": 4, "num_limited_groups": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "route_norm": True, "route_scale": 2.826,
    "score_func": "sigmoid", "sliding_window": 2048,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 200192}
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
CELL = "trinitymini-fsa-1c"
NEW_READERS = ["window_attn_ms", "window_attn_roofline_pct",
               "global_attn_roofline_pct", "gqa_proj_ms"]


def registry():
    return Registry(ROOT, extra=[AFMOE, TINY])


def real_cell():
    return Registry(ROOT).cell(CELL)


@pytest.mark.parametrize("key", sorted(CATALOG))
def test_configuration_keeps_every_published_key(key):
    config = real_cell()["config"]
    if key in REDUCED:
        assert config[key] != CATALOG[key]
        assert config["published"][key] == CATALOG[key]
    else:
        assert config[key] == CATALOG[key], key


def test_configuration_states_its_cut():
    cell = real_cell()
    config, family = cell["config"], cell["family"]
    assert config["reduced"] == REDUCED
    assert config["num_experts"] == 16 and config["router_experts"] == 128
    assert config["expert_offset"] == 0
    assert config["vocab_size"] * 8 == CATALOG["vocab_size"]
    assert config["kept_layers"] == [1, 2, 3, 4, 5]
    assert "8 chips" in config["deployment"]
    assert (config["sequence_length"], config["per_chip_batch"],
            config["precision"], config["data_steps"]) == (
        8192, 2, "bfloat16", 16)
    assert family.layer_kinds(config) == (
        ("window", "mlp"), ("window", "moe"), ("global", "moe"),
        ("window", "moe"), ("window", "moe"))
    # what config.json does not give is said to be assumed, one line each
    for key in ("output_gate", "qk_norms", "four_norms", "rotary",
                "embedding_multiplier", "band_edge"):
        assert "not in config.json" in config["assumed"][key], key
    assert "selection bias" in config["assumed"]["moe"]
    entry = [c for c in Registry(ROOT).spec["configs"]
             if c["name"] == "trinity-mini-ep8"][0]
    assert entry["reduced"] == REDUCED
    assert entry["source"] == config["source"]
    path = os.path.join("/opt/skills/guides/model-configs",
                        "architectures.jsonl")
    if os.path.exists(path):        # the literal above is the row's own
        with open(path) as f:
            rows = [json.loads(line) for line in f]
        row = [r for r in rows if r["name"] == "Trinity-Mini"]
        assert row[0]["config"] == CATALOG
        assert row[0]["source_url"] == config["source"]


def test_the_cell_trains_at_the_rate_the_issue_names():
    config = real_cell()["config"]
    assert config["optimizer"] == {"name": "adam", "lr": 1e-5, "b1": 0.9,
                                   "b2": 0.999, "eps": 1e-8}
    family = real_cell()["family"]
    assert family.weight_std(("layer2", "ffn", "core", "router_kernel"),
                             (2048, 128)) == pytest.approx(2048 ** -0.5)
    assert family.weight_std(("embedding",), (25024, 2048)) == 0.02


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
    assert count(shapes) == stated["total"] == 705_473_792
    assert stated["total"] == (
        stated["dense_layer"] + 4 * stated["expert_layer"]
        + stated["embedding_plus_head"] + stated["final_norm"])
    assert stated["dense_layer"] == (stated["attention"]
                                     + stated["block_norms"]
                                     + stated["dense_mlp"]) == 65_020_160
    assert stated["expert_layer_outside_routed"] == (
        stated["attention"] + stated["block_norms"] + stated["shared_expert"]
        + stated["router"]) == 33_825_024
    assert stated["expert_layer"] == (stated["expert_layer_outside_routed"]
                                      + 16 * stated["routed_expert"])
    assert count(shapes["layer1"]["mixer"]["core"]) == stated["attention"]
    assert count(shapes["layer1"]["ffn"]["core"]) == stated["dense_mlp"]
    assert count(shapes["layer1"]) == stated["dense_layer"]
    assert count(shapes["layer3"]) == stated["expert_layer"]
    experts = count({k: v for k, v in shapes["layer2"]["ffn"]["core"].items()
                     if k.startswith("experts_")})
    assert experts == 16 * stated["routed_expert"]
    assert (count(shapes["embedding"]) + count(shapes["head_kernel"])
            == stated["embedding_plus_head"])
    # k and v keep their 4 heads; every RMSNorm weight is a leaf named
    # `scale` (weights.py makes ones)
    assert shapes["layer3"]["mixer"]["core"]["k_kernel"].shape == (2048, 512)
    names = [p[-1].key for p, _ in
             jax.tree_util.tree_flatten_with_path(shapes)[0]]
    assert names.count("scale") == 5 * (4 + 2) + 1


def test_flops_and_pairs_from_shapes():
    cell = real_cell()
    family, config = cell["family"], cell["config"]
    assert family.seen_pairs(8192, 2048) == 14_681_088
    assert family.seen_pairs(8192, None) == 33_558_528
    assert family.seen_pairs(8192, 9000) == 33_558_528
    window = family.window_attention_shape(config)
    assert window == {"batch": 2, "heads": 32, "kv_heads": 4, "length": 8192,
                      "qk_dim": 128, "v_dim": 128, "pairs": 14_681_088,
                      "layers": 4}
    assert family.window_attention_flops_per_step(window) == (
        1536 * 14_681_088 * 2 * 32 * 4)
    full = family.global_attention_shape(config)
    assert (full["pairs"], full["layers"]) == (33_558_528, 1)
    assert family.global_attention_flops_per_step(full) == (
        1536 * 33_558_528 * 2 * 32)
    # 29.3 and 16.7 ms a step at the bf16 peak (ISSUE 33)
    assert family.window_attention_flops_per_step(window) / 197e12 == \
        pytest.approx(29.30e-3, rel=1e-3)
    assert family.global_attention_flops_per_step(full) / 197e12 == \
        pytest.approx(16.75e-3, rel=1e-3)
    per_token = family.forward_flops_per_token(config)
    assert family.train_flops_per_sample(config) == 3 * 8192 * per_token
    # outside the cores: 5 layers' projections, the dense MLP, 4 expert
    # layers of router + shared + one routed expert a token, the head
    outside = (5 * 2 * 2048 * (3 * 4096 + 2 * 512) + 6 * 2048 * 6144
               + 4 * (2 * 2048 * 128 + 6 * 2048 * 1024 * 2) + 2 * 2048 * 25024)
    cores = 512 * 32 * (4 * 14_681_088 + 33_558_528) / 8192
    assert per_token == pytest.approx(outside + cores, rel=1e-12)
    # 27 TFLOP a step outside the cores, 9.07 inside (ISSUE 33)
    assert 3 * 16384 * outside == pytest.approx(27.2e12, rel=0.01)
    assert 3 * 16384 * cores == pytest.approx(5.77e12 + 3.30e12, rel=0.01)
    # the BERT and MLA roofline readers must not apply to this family
    assert not hasattr(family, "attention_shape")
    assert not hasattr(family, "latent_attention_shape")


def test_data_is_tokens_of_the_slice_with_the_next_token_as_label():
    cell = real_cell()
    x, y = cell["family"].make_data(cell["config"],
                                    np.random.default_rng(2 ** 31 + 5), 3)
    assert x.shape == y.shape == (3, 8192) and x.dtype == np.int32
    assert 0 <= x.min() and x.max() < 25024
    assert np.array_equal(x[:, 1:], y[:, :-1])


def test_the_cells_files_say_where_each_limit_comes_from():
    cell = real_cell()
    workload = cell["workload"]
    assert (workload["log_every"], workload["trace_segments"]) == (1, 3)
    assert cell["traffic_name"] == "fsa-dense-1x1" and cell["chips"] == 1
    assert workload["first_grad_floor"]["value"] > 0
    assert set(workload["limits"]) == {
        "loss_gap", "first_grad_gap", "delta_gap", "nonfinite_losses",
        "compiles_in_window", "first_grad_error"}
    for name, limit in workload["limits"].items():
        assert limit["from"], name
        assert "placeholder" not in limit["from"], name
    assert workload["limits"]["first_grad_error"]["limit"] < 0.5


def rehearse(name, seed):
    return run.run_cell(registry(), name, seed, 30.0, False,
                        rehearse_segments=3)


def test_the_whole_tiny_decoder_through_fit_meets_the_reference(capsys):
    """float32 program: `Trainer.fit` (loader, the model's own loss, FSA's
    dense tier, Adam) against `reference_steps` on the plain reference, to
    rounding, over three steps."""
    result = rehearse("tiny-afmoe-f32", 2 ** 31 + 77)
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
    cell = registry().cell("tiny-afmoe-f32")
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
    sound = check.compare(program, reference, 0)
    control = check.compare(lower, reference, 0)
    assert control["first_grad_error"] > 2 * sound["first_grad_error"]
    limits = {name: {"limit": limit} for name, limit in [
        ("loss_gap", 0.03), ("first_grad_gap", 0.3), ("delta_gap", 0.5),
        ("first_grad_error", 1.5 * sound["first_grad_error"])]}
    assert check.verdict(sound, limits)[0] is True, sound
    assert check.verdict(control, limits)[0] is False, control


@pytest.mark.parametrize("name", NEW_READERS + [
    "moe_experts_ms", "lm_loss_ms", "moe_dropped_pct"])
def test_readers_apply_to_this_family_and_read_nothing_from_nothing(name):
    reg = Registry(ROOT)
    reader = {m.NAME: m for m in reg.layer_metrics()}[name]
    assert reader.applies(reg.cell(CELL))
    assert not reader.applies(reg.cell("bertlarge-fsa-1c"))
    assert not reader.applies(reg.cell("resnet18-bsc-1c"))
    assert reader.applies(reg.cell("kimilinear-fsa-1c")) == (
        name not in NEW_READERS)
    entry = [m for m in reg.spec["per_layer"] if m["name"] == name][0]
    assert CELL in entry["workloads"]
    assert entry["moves"] == "samples_per_s_chip"
    # no trace, no table, no counters (the parent's program): None, no raise
    ctx = {"cell": reg.cell(CELL), "trace": None,
           "loop_stats": {"steps": 4, "wall_s": 1.0, "phases": {}},
           "step_layers": None, "peaks": {"bf16_flops_per_s": 197e12,
                                          "hbm_bytes_per_s": 819e9}}
    assert reader.read(ctx) is None


@pytest.mark.parametrize("name", ["flash_attn_roofline_pct",
                                  "latent_attn_roofline_pct", "attention_ms",
                                  "kda_scan_ms", "kda_scan_roofline_pct"])
def test_other_families_roofline_readers_do_not_apply(name):
    reg = Registry(ROOT)
    reader = {m.NAME: m for m in reg.layer_metrics()}[name]
    assert not reader.applies(reg.cell(CELL))


def test_scope_readers_join_the_trace_with_the_programs_table():
    from geomx_tpu.telemetry.layers import OpLayer
    reg = Registry(ROOT)
    readers = {m.NAME: m for m in reg.layer_metrics()}
    fb = "step/forward_backward/"
    table = {
        "custom.1": OpLayer(fb + "gqa/window/attn/core", "kernels",
                            "forward"),
        "custom.2": OpLayer(fb + "gqa/window/attn/core", "kernels",
                            "backward"),
        "custom.3": OpLayer(fb + "gqa/global/attn/core", "kernels",
                            "backward"),
        "fusion.4": OpLayer(fb + "gqa/proj", "step program", "forward"),
        "fusion.5": OpLayer(fb + "moe/experts", "step program", "forward"),
        "fusion.6": OpLayer(fb + "lm/loss", "step program", "forward")}
    ctx = {"cell": reg.cell(CELL), "step_layers": table,
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "trace": {"steps": 2, "by_op_s": {
               "custom.1": 0.05, "custom.2": 0.15, "custom.3": 0.1,
               "fusion.4": 0.08, "fusion.5": 0.02, "fusion.6": 0.04,
               "not.in.table": 9.0}}}
    assert readers["window_attn_ms"].read(ctx) == pytest.approx(100.0)
    assert readers["gqa_proj_ms"].read(ctx) == pytest.approx(40.0)
    assert readers["moe_experts_ms"].read(ctx) == pytest.approx(10.0)
    assert readers["lm_loss_ms"].read(ctx) == pytest.approx(20.0)
    # 5.77 TFLOP / 197 TFLOP/s = 29.30 ms of 100; 3.30 / 197 = 16.75 of 50
    assert readers["window_attn_roofline_pct"].read(ctx) == pytest.approx(
        29.304, rel=1e-3)
    assert readers["global_attn_roofline_pct"].read(ctx) == pytest.approx(
        100 * 16.746 / 50.0, rel=1e-3)
    ctx["loop_stats"] = {"steps": 8, "counters": {
        "moe/dropped": {"count": 4, "total": 0.0, "last": 0.0, "max": 0.0},
        "moe/assignments_mean": {"count": 4, "total": 4096.0, "last": 1024.0,
                                 "max": 1024.0}}}
    assert readers["moe_dropped_pct"].read(ctx) == 0.0
    # 4 steps x 4 layers x 16 held x 1,024 arrived; 1% of them dropped
    ctx["loop_stats"]["counters"]["moe/dropped"]["total"] = 2621.44
    assert readers["moe_dropped_pct"].read(ctx) == pytest.approx(1.0)


def test_every_program_key_of_the_file_reaches_the_model():
    """`program` records what was chosen to make the cell fit; a key that
    `build_model` did not read would drift from the code in silence."""
    cell = real_cell()
    config = json.loads(json.dumps(cell["config"]))
    model = cell["family"].build_model(config).cfg
    program = config["program"]
    assert (model.loss_block, model.expert_rows, model.expert_pool,
            model.remat) == (
        program["loss_block_tokens"], program["expert_block_rows"],
        program["expert_pool_places"], program["remat_each_layer"])
    assert set(program) == {"loss_block_tokens", "expert_block_rows",
                            "expert_pool_places", "remat_each_layer", "note"}
    # twice what even routing sends the 16 held experts
    assert model.expert_pool == 2 * 16384 * 8 * 16 // 128 == 32768
    config["program"] = dict(program, loss_block_tokens=512,
                             expert_block_rows=128, expert_pool_places=4096,
                             remat_each_layer=False)
    other = cell["family"].build_model(config).cfg
    assert (other.loss_block, other.expert_rows, other.expert_pool,
            other.remat) == (512, 128, 4096, False)
    assert (model.window, model.num_kv_heads, model.embedding_scale) == (
        2048, 4, pytest.approx(2048 ** 0.5))


def test_the_first_pool_has_a_size_of_its_own_and_a_default_that_keeps():
    """`ops/held_experts`: no pool given, 2 E rows (the Kimi cell's 8,192
    places); a pool given, that many, in whole tiles."""
    from geomx_tpu.ops.held_experts import _pools
    assert _pools(8, 512, 16384 * 8) == (8192, 1024, 8192 + 120 * 1024)
    assert _pools(16, 512, 16384 * 8, 32768) == (32768, 1024, 131072)
    assert _pools(16, 1024, 16384 * 8)[0] == 32768
    with pytest.raises(ValueError, match="whole"):
        _pools(16, 512, 1000, 700)
    kimi = Registry(ROOT).cell("kimilinear-fsa-1c")
    assert kimi["family"].build_model(kimi["config"]).cfg.expert_pool is None
