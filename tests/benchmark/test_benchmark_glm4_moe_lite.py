"""The `glm4_moe_lite` family and its cell: the configuration file against
the catalog row it was cut from, the parameter table counted from the
built model, the FLOP counts from shapes, the whole tiny decoder through
`Trainer.fit` against `reference_steps` under the harness, the float8
control, the module's loss left out of the total, the two readers this
family brought and the accepted ones that apply to the cell, and the
`program` keys.  Whatever cells `BENCHMARK.json` lists are taken from the
file: no set of cell names and no position in `per_layer` is written
here."""
import functools
import json
import os

import numpy as np
import pytest

from bench_paths import ROOT, TINY

from benchmark import check, run
from benchmark.cells import Registry

GLM = os.path.join(ROOT, "tests", "benchmark", "data_glm4_moe_lite")

# `config` of the catalog's row `GLM-4.7-Flash` (model-configs guide,
# architectures.jsonl), copied whole
CATALOG = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 10240, "max_position_embeddings": 202752,
    "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
    "first_k_dense_replace": 1, "num_hidden_layers": 47,
    "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 1000000, "tie_word_embeddings": False, "q_lora_rank": 768,
    "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
    "v_head_dim": 256, "vocab_size": 154880}
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
HELD = {"num_hidden_layers": 5, "n_routed_experts": 8, "vocab_size": 19360}
CELL = "glm47flash-fsa-1c"
CONFIG = "glm-4.7-flash-ep8"
TINY_CELL = "tiny-glm-f32"
# the accepted readers whose `applies` takes this family and which find
# something to read in its step: an expert layer with a shared expert
# (`layer_kinds`) and a latent attention shape
EXPERT_READERS = ["moe_experts_ms", "moe_dispatch_ms", "moe_route_ms",
                  "moe_shared_ms", "moe_dropped_pct", "lm_loss_ms"]
ATTENTION_READERS = ["latent_attn_roofline_pct", "attention_ms"]
NEW_READERS = ["mtp_ms", "mla_proj_ms"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
SPEC = Registry(ROOT).spec
CELLS = [w["name"] for w in SPEC["workloads"]]


def registry():
    return Registry(ROOT, extra=[GLM, TINY])


def real_cell():
    return Registry(ROOT).cell(CELL)


def readers():
    return {m.NAME: m for m in Registry(ROOT).layer_metrics()}


@pytest.mark.parametrize("key", sorted(CATALOG))
def test_configuration_keeps_every_published_key(key):
    config = real_cell()["config"]
    if key in REDUCED:
        assert config[key] == HELD[key] != CATALOG[key]
        assert config["published"][key] == CATALOG[key]
    else:
        assert config[key] == CATALOG[key], key


def test_configuration_states_its_cut():
    cell = real_cell()
    config, family = cell["config"], cell["family"]
    assert config["reduced"] == REDUCED == list(config["published"])
    assert config["n_routed_experts"] == 8 and config["router_experts"] == 64
    assert config["expert_offset"] == 0
    assert config["vocab_size"] * 8 == CATALOG["vocab_size"]
    assert config["n_routed_experts"] * 8 == CATALOG["n_routed_experts"]
    assert config["kept_layers"] == [0, 1, 2, 3, 4]
    assert "8 chips" in config["deployment"]
    assert "layer 47" in config["kept_layers_note"]
    assert (config["sequence_length"], config["per_chip_batch"],
            config["precision"], config["data_steps"]) == (
        16384, 1, "bfloat16", 16)
    # the dense lead and the floor of four layers behind it; the module
    # stays at its published depth
    assert family.layer_kinds(config) == (
        ("mla", "mlp"),) + (("mla", "moe"),) * 4
    assert config["num_nextn_predict_layers"] == 1
    assert config["mtp_loss_weight"] == 0.3
    # what config.json does not give is said to be assumed, one line each
    for key in ("block", "attention", "moe"):
        assert "not in config.json" in config["assumed"][key], key
    for key in ("rotary", "rotary_pairing", "mtp", "mtp_weight", "mtp_join",
                "mtp_input", "sequences", "weights", "what_it_is"):
        assert config["assumed"][key], key
    assert "2412.19437" in config["assumed"]["mtp"]
    assert "2508.06471" in config["assumed"]["mtp"]
    assert "0.3" in config["assumed"]["mtp_weight"]
    entry = [c for c in SPEC["configs"] if c["name"] == CONFIG][0]
    assert entry["reduced"] == REDUCED
    assert entry["source"] == config["source"]
    path = os.path.join("/opt/skills/guides/model-configs",
                        "architectures.jsonl")
    if os.path.exists(path):        # the literal above is the row's own
        with open(path) as f:
            rows = [json.loads(line) for line in f]
        row = [r for r in rows if r["name"] == "GLM-4.7-Flash"]
        assert row[0]["config"] == CATALOG
        assert row[0]["source_url"] == config["source"]


def test_no_width_is_reduced():
    config = real_cell()["config"]
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_experts_per_tok", "num_attention_heads",
                "num_key_value_heads", "q_lora_rank", "kv_lora_rank",
                "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                "n_shared_experts", "num_nextn_predict_layers"):
        assert config[key] == CATALOG[key] and key not in REDUCED, key


def test_sizes_are_the_configurations_keys():
    cell = real_cell()
    s = cell["family"].sizes(cell["config"])
    assert (s["hidden"], s["num_heads"], s["q_rank"], s["kv_rank"],
            s["qk_nope_dim"], s["qk_rope_dim"], s["v_head_dim"],
            s["rope_theta"], s["dense_width"], s["expert_width"],
            s["num_experts"], s["experts_held"], s["expert_offset"],
            s["top_k"], s["routed_scaling"], s["shared_experts"],
            s["mtp_depth"], s["mtp_weight"], s["eps"]) == (
        2048, 20, 768, 512, 192, 64, 256, 1e6, 10240, 1536, 64, 8, 0, 4,
        1.8, 1, 1, 0.3, 1e-5)
    model = cell["family"].build_model(cell["config"]).cfg
    assert (model.post_norms, model.embedding_scale, model.expert_form,
            model.mtp_block) == (False, 1.0, {}, ("mla", "moe"))
    mixer = model.make_mixer("mla", None)
    assert (mixer.q_rank, mixer.rope, mixer.v_dim) == (768, 1e6, 256)
    # a configuration the family cannot run is refused, not bent
    for key, value, said in (("rope_scaling", {"type": "yarn"}, "rotary"),
                             ("n_group", 8, "group"),
                             ("norm_topk_prob", False, "renormalised")):
        bad = json.loads(json.dumps(cell["config"]))
        bad[key] = value
        with pytest.raises(ValueError, match=said):
            cell["family"].sizes(bad)


def test_the_cell_trains_at_the_rate_and_seeding_the_file_names():
    cell = real_cell()
    assert cell["config"]["optimizer"] == {
        "name": "adam", "lr": 1e-5, "b1": 0.9, "b2": 0.999, "eps": 1e-8}
    family = cell["family"]
    for path, shape in ((("layer2", "ffn", "core", "router_kernel"),
                         (2048, 64)),
                        (("mtp1", "block", "ffn", "core",
                          "experts_down_kernel"), (8, 1536, 2048)),
                        (("mtp1", "join_kernel"), (4096, 2048)),
                        (("layer1", "mixer", "core", "q_b_kernel"),
                         (768, 5120))):
        assert family.weight_std(path, shape) == pytest.approx(
            shape[-2] ** -0.5), path
    assert family.weight_std(("embedding",), (19360, 2048)) == \
        family.EMBEDDING_STD
    assert str(family.EMBEDDING_STD) in cell["config"]["assumed"]["weights"]


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
    assert count(shapes) == stated["total"] == 706_518_528
    assert stated["total"] == (
        stated["dense_layer"] + 4 * stated["expert_layer"]
        + stated["mtp_module"] + stated["embedding_plus_head"]
        + stated["final_norm"])
    assert stated["latent_mixer"] == 21_759_232 == (
        2048 * 768 + 768 + 768 * 5120 + 2048 * 576 + 512 + 512 * 20 * 448
        + 5120 * 2048)
    assert stated["dense_layer"] == 84_677_888 == (
        stated["latent_mixer"] + stated["block_norms"] + stated["dense_mlp"])
    assert stated["dense_mlp"] == 3 * 2048 * 10240
    assert stated["moe_outside_routed"] == 2048 * 64 + 3 * 2048 * 1536
    assert stated["routed_expert"] == 3 * 2048 * 1536 == 9_437_184
    assert stated["expert_layer"] == 106_829_056 == (
        stated["latent_mixer"] + stated["block_norms"]
        + stated["moe_outside_routed"] + 8 * stated["routed_expert"])
    assert stated["mtp_module"] == 115_223_808 == (
        4096 + 4096 * 2048 + stated["expert_layer"] + 2048)
    assert stated["embedding_plus_head"] == 2 * 19360 * 2048
    assert count(shapes["layer1"]) == stated["dense_layer"]
    for i in range(2, 6):
        assert count(shapes[f"layer{i}"]) == stated["expert_layer"], i
    assert count(shapes["mtp1"]) == stated["mtp_module"]
    assert count(shapes["mtp1"]["block"]) == stated["expert_layer"]
    assert sorted(shapes["mtp1"]) == ["block", "hidden_norm", "join_kernel",
                                      "out_norm", "token_norm"]
    for block in [shapes[f"layer{i}"] for i in range(1, 6)] + [
            shapes["mtp1"]["block"]]:
        core = block["mixer"]["core"]
        assert count(core) == stated["latent_mixer"]
        assert sorted(core) == ["kv_a_kernel", "kv_b_kernel", "kv_norm",
                                "out_kernel", "q_a_kernel", "q_b_kernel",
                                "q_norm"]
        assert sorted(block["mixer"]) == sorted(block["ffn"]) == [
            "core", "norm"]             # no post-norms
    assert shapes["layer3"]["ffn"]["core"]["experts_up_kernel"].shape == (
        8, 2048, 1536)
    assert shapes["layer3"]["ffn"]["core"]["router_kernel"].shape == (
        2048, 64)
    assert shapes["mtp1"]["join_kernel"].shape == (4096, 2048)
    # the module shares embedding and head: it has none of its own
    names = ["/".join(k.key for k in p) for p, _ in
             jax.tree_util.tree_flatten_with_path(shapes)[0]]
    assert [n for n in names if "embedding" in n or "head" in n] == [
        "embedding", "head_kernel"]
    assert sum(n.endswith("scale") for n in names) == 6 * (2 + 2) + 3 + 1
    # 11.30 GB at the program's 16 B a parameter
    assert 16 * stated["total"] == pytest.approx(11.30e9, rel=1e-3)


def test_flops_from_shapes():
    cell = real_cell()
    family, config = cell["family"], cell["config"]
    shape = family.latent_attention_shape(config)
    assert shape == {"batch": 1, "heads": 20, "length": 16384, "qk_dim": 256,
                     "v_dim": 256, "layers": 6}
    cores = family.latent_attention_flops_per_step(shape)
    assert cores == 1536 * 20 * 16384 ** 2 * 6
    assert cores == pytest.approx(49.48e12, rel=1e-3)
    per_token = family.forward_flops_per_token(config)
    assert family.train_flops_per_sample(config) == 3 * 16384 * per_token
    mixer = 2 * (2048 * 768 + 768 * 5120 + 2048 * 576 + 512 * 20 * 448
                 + 5120 * 2048)
    core = 512 * 16384 * 20             # (256 + 256) L H: the causal half
    # 0.5 held picks a token (8 x 4 / 64) beside the shared expert
    moe = 2 * 2048 * 64 + 6 * 2048 * 1536 * 1.5
    head = 2 * 2048 * 19360
    want = (6 * (mixer + core) + 6 * 2048 * 10240 + 5 * moe
            + 2 * 4096 * 2048 + 2 * head)
    assert per_token == pytest.approx(want, rel=1e-12)
    # 84.1 TFLOP a step, 59% of them the cores, 74% the mixers (ISSUE 45)
    total = family.train_flops_per_sample(config)
    assert total == pytest.approx(84.14e12, rel=1e-3)
    assert cores / total == pytest.approx(0.588, abs=2e-3)
    assert (cores + 3 * 16384 * 6 * mixer) / total == pytest.approx(
        0.741, abs=2e-3)
    # without the module's block, join and head pass a sixth less
    less = json.loads(json.dumps(config))
    less["num_nextn_predict_layers"] = 0
    assert family.forward_flops_per_token(less) == pytest.approx(
        per_token - (mixer + core + moe + 2 * 4096 * 2048 + head), rel=1e-12)
    assert family.latent_attention_shape(less)["layers"] == 5
    for name in ("attention_shape", "window_attention_shape",
                 "kda_scan_shape", "ssd_scan_shape"):
        assert not hasattr(family, name), name


def test_data_is_tokens_of_the_slice_with_the_next_token_as_label():
    cell = real_cell()
    x, y = cell["family"].make_data(cell["config"],
                                    np.random.default_rng(2 ** 31 + 5), 3)
    assert x.shape == y.shape == (3, 16384) and x.dtype == np.int32
    assert 0 <= x.min() and x.max() < 19360
    assert np.array_equal(x[:, 1:], y[:, :-1])


def test_the_cells_files_say_where_each_limit_comes_from():
    cell = real_cell()
    workload = cell["workload"]
    assert workload["log_every"] >= 1 and workload["trace_segments"] >= 1
    assert "spread" in workload["segments_from"]
    assert cell["traffic_name"] == "fsa-dense-1x1" and cell["chips"] == 1
    assert workload["first_grad_floor"]["value"] > 0
    assert set(workload["limits"]) == {
        "loss_gap", "first_grad_gap", "delta_gap", "nonfinite_losses",
        "compiles_in_window", "first_grad_error"}
    for name, limit in workload["limits"].items():
        assert limit["from"], name
        assert "TO BE SET" not in limit["from"], name
    for name in ("loss_gap", "first_grad_gap", "delta_gap",
                 "first_grad_error"):
        said = workload["limits"][name]["from"]
        assert "sound" in said and "seeds" in said, name
        assert "control" in said or "planted" in said, name
    for name in ("loss_gap", "first_grad_gap", "first_grad_error"):
        assert "left out" in workload["limits"][name]["from"], name
    assert "control" in workload["limits"]["first_grad_error"]["from"]
    assert workload["limits"]["first_grad_error"]["limit"] < 0.3
    entry = Registry(ROOT).workloads[CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "fsa-dense-1x1", 1)
    assert "8x" in entry["why"] and "1/8" in entry["why"]


def rehearse(name, seed):
    return run.run_cell(registry(), name, seed, 30.0, False,
                        rehearse_segments=3)


def test_the_whole_tiny_decoder_through_fit_meets_the_reference(capsys):
    """float32 program: `Trainer.fit` (loader, the model's own weighted
    loss, FSA's dense tier, Adam) against `reference_steps` on the plain
    reference, to rounding, over three steps."""
    result = rehearse(TINY_CELL, 2 ** 31 + 77)
    out = capsys.readouterr().out
    assert result["correct"] is True, out
    checks = result["checks"]
    assert checks["first_grad_error"]["value"] < 1e-4
    assert checks["loss_gap"]["value"] < 1e-5
    assert checks["delta_gap"]["value"] < 1e-3
    # the model's counters came through the window's LoopStats
    stats = json.loads([line for line in out.splitlines()
                        if line.startswith("LOOP_STATS ")][0][11:])
    counters = stats["counters"]
    assert counters["moe/dropped"]["total"] == 0.0
    assert counters["moe/assignments_mean"]["count"] == 3
    assert counters["mtp/loss"]["count"] == 3
    assert 0 < counters["mtp/loss"]["last"] != counters["lm/main_loss"]["last"]


@functools.lru_cache(maxsize=None)
def tiny_readings():
    """The tiny cell at bfloat16: the program's readings, the float8
    control's and the module's loss left out, each against the float32
    reference."""
    from benchmark.references.numerics import next_lower
    cell = registry().cell(TINY_CELL)
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
    without = dict(cell, config=dict(config, mtp_loss_weight=0.0))
    left_out = run.run_reference(without, shapes, x, y, seed)
    return {name: check.compare(side, reference, 0) for name, side in (
        ("sound", program), ("control", lower), ("left_out", left_out))}


def test_the_bfloat16_program_is_sound_and_the_float8_control_is_not():
    """Limits can sit between the program's readings and the control's
    (the plain reference at float8 in the program's place), as the chip
    cell's do at its own size."""
    sound, control = tiny_readings()["sound"], tiny_readings()["control"]
    assert control["first_grad_error"] > 2 * sound["first_grad_error"]
    limits = {name: {"limit": limit} for name, limit in [
        ("loss_gap", 0.03), ("first_grad_gap", 0.3), ("delta_gap", 0.5),
        ("first_grad_error", 1.5 * sound["first_grad_error"])]}
    assert check.verdict(sound, limits)[0] is True, sound
    assert check.verdict(control, limits)[0] is False, control


def test_the_modules_loss_left_out_of_the_total_is_not_correct():
    """lambda 0 in the program's place: the loss is short by the module's
    part, the module's own leaves get no gradient, and what the module
    sent down into embedding, head and layers is missing: the loss and
    both numbers of the first gradient fail the same limits the sound
    program meets."""
    sound, left_out = tiny_readings()["sound"], tiny_readings()["left_out"]
    assert left_out["loss_gap"] == pytest.approx(0.23, abs=0.03)
    assert left_out["first_grad_gap"] == pytest.approx(1.0)
    assert left_out["first_grad_error"] > 0.1
    limits = {name: {"limit": limit} for name, limit in [
        ("loss_gap", 0.03), ("first_grad_gap", 0.3), ("delta_gap", 0.5),
        ("first_grad_error", 1.5 * sound["first_grad_error"])]}
    failed = [line["number"] for line in check.verdict(left_out, limits)[1]
              if not line["ok"]]
    assert {"loss_gap", "first_grad_gap", "first_grad_error"} <= set(failed)


@functools.lru_cache(maxsize=None)
def tiny_faults():
    from benchmark.tools import mtp_left_out
    return mtp_left_out.read_faults(registry().cell(TINY_CELL), 2 ** 31 + 77)


@pytest.mark.parametrize("fault, number, least, most", [
    ("mtp_loss_left_out", "loss_gap", 0.15, 0.3),
    ("mtp_loss_left_out", "first_grad_gap", 1.0, 1.0),
    ("mtp_loss_left_out", "first_grad_error", 0.1, 1.0),
    ("gradient_scaled_by_half", "first_grad_gap", 0.5, 0.5),
    ("gradient_scaled_by_half", "first_grad_error", 0.5, 0.5),
    ("one_leaf_missing", "first_grad_gap", 1.0, 1.0),
    ("state_unchanged", "delta_gap", 1.0, 1.0),
])
def test_a_planted_fault_reads_what_the_limits_are_set_against(
        fault, number, least, most):
    """`benchmark/tools/mtp_left_out.py` on the tiny cell: the module's
    loss left out, and the three faults `planted_faults.planted()` plants
    in the reference's own readings; each moves the number that is there
    to catch it, and fails the limits."""
    numbers = tiny_faults()[fault]
    assert least - 1e-6 <= numbers[number] <= most + 1e-6, numbers
    limits = registry().cell(TINY_CELL)["workload"]["limits"]
    assert check.verdict(numbers, limits)[0] is False


def test_the_tool_refuses_a_cell_with_no_module():
    from benchmark.tools import mtp_left_out
    with pytest.raises(SystemExit, match="no module"):
        mtp_left_out.read_faults(Registry(ROOT).cell("kimilinear-fsa-1c"), 1)


def empty_context(cell):
    """No trace, no table, no counters (the parent's program)."""
    return {"cell": cell, "trace": None,
            "loop_stats": {"steps": 4, "wall_s": 1.0, "phases": {}},
            "step_layers": None, "peaks": PEAKS}


@pytest.mark.parametrize("name", EXPERT_READERS + ATTENTION_READERS
                         + NEW_READERS)
def test_readers_apply_where_listed_and_read_nothing_from_nothing(name):
    reg = Registry(ROOT)
    reader = readers()[name]
    entry = [m for m in SPEC["per_layer"] if m["name"] == name][0]
    assert CELL in entry["workloads"]
    assert reader.applies(reg.cell(CELL))
    assert entry["moves"] == "samples_per_s_chip"
    assert entry["unit"] == reader.UNIT
    assert reader.read(empty_context(reg.cell(CELL))) is None


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("name", NEW_READERS)
def test_the_new_readers_apply_exactly_where_they_are_listed(name, cell):
    """Over whatever cells the file lists: `mtp_ms` where the
    configuration has a module, `mla_proj_ms` where the family has a
    latent attention shape."""
    reg = Registry(ROOT)
    loaded = reg.cell(cell)
    entry = [m for m in SPEC["per_layer"] if m["name"] == name][0]
    assert (entry["source"], entry["layer"], entry["better"]) == (
        "device_trace", "step program", "lower")
    want = {"mtp_ms": loaded["config"].get("num_nextn_predict_layers", 0) > 0,
            "mla_proj_ms": hasattr(loaded["family"],
                                   "latent_attention_shape")}[name]
    assert readers()[name].applies(loaded) == want
    assert (cell in entry["workloads"]) == want


def test_other_families_readers_do_not_apply():
    reg = Registry(ROOT)
    cell = reg.cell(CELL)
    applying = {m.NAME for m in reg.layer_metrics() if m.applies(cell)}
    always = {m["name"] for m in SPEC["per_layer"] if "workloads" not in m}
    assert applying == always | set(
        EXPERT_READERS + ATTENTION_READERS + NEW_READERS)


def test_scope_readers_join_the_trace_with_the_programs_table():
    """The two new readers by hand on a made table: a module's
    instructions count under `mtp/module` AND under the scopes nested in
    it, so `mla_proj_ms`, `attention_ms`, `moe_experts_ms` and
    `lm_loss_ms` hold the module's part too."""
    from geomx_tpu.telemetry.layers import OpLayer
    reg = Registry(ROOT)
    fb = "step/forward_backward/"
    table = {
        "fusion.1": OpLayer(fb + "mla/proj", "step program", "forward"),
        "fusion.2": OpLayer(fb + "mla/proj", "step program", "backward"),
        "custom.3": OpLayer(fb + "mla/attention/attn/core", "kernels",
                            "backward"),
        "fusion.4": OpLayer(fb + "mtp/module/mtp/combine", "step program",
                            "forward"),
        "fusion.5": OpLayer(fb + "mtp/module/mla/proj", "step program",
                            "backward"),
        "custom.6": OpLayer(fb + "mtp/module/mla/attention/attn/core",
                            "kernels", "forward"),
        "fusion.7": OpLayer(fb + "mtp/module/moe/experts", "step program",
                            "forward"),
        "fusion.8": OpLayer(fb + "mtp/module/lm/loss", "step program",
                            "backward"),
        "fusion.9": OpLayer(fb + "lm/loss", "step program", "forward"),
        "fusion.10": OpLayer(fb + "moe/shared", "step program", "forward")}
    ctx = {"cell": reg.cell(CELL), "step_layers": table, "peaks": PEAKS,
           "trace": {"steps": 2, "by_op_s": {
               "fusion.1": 0.04, "fusion.2": 0.06, "custom.3": 0.5,
               "fusion.4": 0.01, "fusion.5": 0.02, "custom.6": 0.1,
               "fusion.7": 0.03, "fusion.8": 0.04, "fusion.9": 0.05,
               "fusion.10": 0.008, "not.in.table": 9.0}}}
    got = {name: readers()[name].read(ctx) for name in (
        "mtp_ms", "mla_proj_ms", "attention_ms", "moe_experts_ms",
        "lm_loss_ms", "moe_shared_ms")}
    assert got["mtp_ms"] == pytest.approx(1e3 * 0.20 / 2)
    assert got["mla_proj_ms"] == pytest.approx(1e3 * 0.12 / 2)
    assert got["attention_ms"] == pytest.approx(1e3 * 0.6 / 2)
    assert got["moe_experts_ms"] == pytest.approx(1e3 * 0.03 / 2)
    assert got["lm_loss_ms"] == pytest.approx(1e3 * 0.09 / 2)
    assert got["moe_shared_ms"] == pytest.approx(1e3 * 0.008 / 2)
    # 49.48 TFLOP / 197 TFLOP/s = 251.2 ms of the 300 under mla/attention
    assert readers()["latent_attn_roofline_pct"].read(ctx) == pytest.approx(
        100 * 251.16 / 300.0, rel=1e-3)
    # a program with no module (the parent, or Kimi's): nothing to read
    for name in list(table):
        if "mtp/" in table[name].scope:
            del table[name]
    assert readers()["mtp_ms"].read(ctx) is None
    assert readers()["mla_proj_ms"].read(ctx) == pytest.approx(50.0)


@pytest.mark.parametrize("context", [
    {"trace": None, "step_layers": {}},
    {"trace": {"steps": 0, "by_op_s": {}}, "step_layers": {}},
    {"trace": {"steps": 2, "by_op_s": {"a": 1.0}}, "step_layers": None},
    {"trace": {"steps": 2, "by_op_s": {"a": 1.0}}, "step_layers": {}},
], ids=["no-trace", "no-steps", "no-table", "no-such-scope"])
@pytest.mark.parametrize("name", NEW_READERS)
def test_the_new_readers_read_none_where_there_is_nothing_to_read(name,
                                                                  context):
    assert readers()[name].read(context) is None


def test_a_roofline_share_cannot_pass_100_at_the_floor_itself():
    from geomx_tpu.telemetry.layers import OpLayer
    reg = Registry(ROOT)
    cell = reg.cell(CELL)
    family, config = cell["family"], cell["config"]
    flops = family.latent_attention_flops_per_step(
        family.latent_attention_shape(config))
    ctx = {"cell": cell, "peaks": PEAKS, "step_layers": {
        "k": OpLayer("step/forward_backward/mla/attention", "kernels",
                     "forward")},
        "trace": {"steps": 1, "by_op_s": {"k": flops / 197e12}}}
    assert readers()["latent_attn_roofline_pct"].read(ctx) == pytest.approx(
        100.0)


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
    # twice what even routing sends the 8 held: 0.5 picks a token
    assert model.expert_pool == 2 * 16384 * 4 * 8 // 64 == 16384
    assert model.expert_pool % model.expert_rows == 0
    config["program"] = dict(program, loss_block_tokens=512,
                             expert_block_rows=128, expert_pool_places=4096,
                             remat_each_layer=False)
    other = cell["family"].build_model(config).cfg
    assert (other.loss_block, other.expert_rows, other.expert_pool,
            other.remat) == (512, 128, 4096, False)
    assert (model.num_heads, model.top_k, model.num_experts,
            model.experts_held, model.mtp_depth) == (20, 4, 64, 8, 1)


def test_the_timing_tools_read_the_new_files_keys():
    """`tools/flash_attention_timing.py latent256` and
    `tools/held_experts_timing.py --config` take their shapes from the
    configuration's own keys."""
    import sys
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import flash_attention_timing as flash
    assert flash.NAMED["latent256"] == (1, 16384, 20, 256, 256, True)
    assert flash.latent_shape(
        "benchmark/configs/kimi-linear-48b-ep32.json") == (
        1, 8192, 32, 192, 128, True) == flash.NAMED["latent"]
    with open(os.path.join(ROOT, "tools", "held_experts_timing.py")) as f:
        assert 'config.get("n_routed_experts"' in f.read()
