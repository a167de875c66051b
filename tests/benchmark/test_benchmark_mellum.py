"""The `mellum` family and its cell: the configuration file against the
catalog row it was cut from, the parameter table counted from the built
model, the FLOP and pair counts from shapes, the whole tiny decoder through
`Trainer.fit` against `reference_steps` under the harness, the float8
control, the accepted per-layer readers that apply to the cell, and the
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

MELLUM = os.path.join(ROOT, "tests", "benchmark", "data_mellum")

WINDOW, FULL = "sliding_attention", "full_attention"
# `config` of the catalog's row `Mellum2-12B-A2.5B-Instruct` (model-configs
# guide, architectures.jsonl), copied whole
CATALOG = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": [WINDOW, WINDOW, WINDOW, FULL] * 7,
    "mlp_layer_types": ["sparse"] * 28, "max_position_embeddings": 131072,
    "max_window_layers": 0, "model_type": "mellum",
    "moe_intermediate_size": 896, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 28, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True}
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
HELD = {"num_hidden_layers": 4, "num_experts": 16, "vocab_size": 24576}
CELL = "mellum2-fsa-1c"
CONFIG = "mellum2-12b-ep4"
# the accepted readers whose `applies` takes this family and which find
# something to read in its step: an expert layer (`layer_kinds`) and window
# / global attention shapes
EXPERT_READERS = ["moe_experts_ms", "moe_dispatch_ms", "moe_route_ms",
                  "moe_dropped_pct", "lm_loss_ms"]
# `applies` asks only for an expert layer, and this one has no shared
# expert: nothing to read, so the cell is not on the metric's list (the
# check refuses a traced line that lacks a listed metric; PERF.md section 7)
SILENT_READERS = ["moe_shared_ms"]
ATTENTION_READERS = ["window_attn_ms", "window_attn_roofline_pct",
                     "global_attn_roofline_pct", "gqa_proj_ms"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
SPEC = Registry(ROOT).spec
CELLS = [w["name"] for w in SPEC["workloads"]]


def registry():
    return Registry(ROOT, extra=[MELLUM, TINY])


def real_cell():
    return Registry(ROOT).cell(CELL)


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
    assert config["num_experts"] == 16 and config["router_experts"] == 64
    assert config["expert_offset"] == 0
    assert config["vocab_size"] * 4 == CATALOG["vocab_size"]
    assert config["num_experts"] * 4 == CATALOG["num_experts"]
    assert config["kept_layers"] == [0, 1, 2, 3]
    assert "4 chips" in config["deployment"]
    assert (config["sequence_length"], config["per_chip_batch"],
            config["precision"], config["data_steps"]) == (
        16384, 1, "bfloat16", 16)
    # one whole period at the published 3 : 1, every layer sparse
    assert family.layer_kinds(config) == (
        ("window", "moe"),) * 3 + (("global", "moe"),)
    assert CATALOG["layer_types"].count(WINDOW) == 3 * \
        CATALOG["layer_types"].count(FULL)
    # what config.json does not give is said to be assumed, one line each
    for key in ("block", "qk_norms", "rotary", "band_edge", "moe"):
        assert "not in config.json" in config["assumed"][key], key
    assert "Qwen3-MoE" in config["assumed"]["qk_norms"]
    assert "no shared expert" in config["assumed"]["moe"]
    assert "none is guessed" in config["assumed"]["mtp"]
    assert config["assumed"]["weights"] and config["assumed"]["sequences"]
    entry = [c for c in SPEC["configs"] if c["name"] == CONFIG][0]
    assert entry["reduced"] == REDUCED
    assert entry["source"] == config["source"]
    path = os.path.join("/opt/skills/guides/model-configs",
                        "architectures.jsonl")
    if os.path.exists(path):        # the literal above is the row's own
        with open(path) as f:
            rows = [json.loads(line) for line in f]
        row = [r for r in rows if r["name"] == "Mellum2-12B-A2.5B-Instruct"]
        assert row[0]["config"] == CATALOG
        assert row[0]["source_url"] == config["source"]


def test_no_width_is_reduced():
    config = real_cell()["config"]
    for key in ("hidden_size", "head_dim", "intermediate_size",
                "moe_intermediate_size", "num_experts_per_tok",
                "num_attention_heads", "num_key_value_heads",
                "sliding_window", "rope_parameters"):
        assert config[key] == CATALOG[key] and key not in REDUCED, key


def test_sizes_are_the_configurations_keys():
    cell = real_cell()
    s = cell["family"].sizes(cell["config"])
    assert (s["hidden"], s["num_heads"], s["num_kv_heads"], s["head_dim"],
            s["window"], s["rope_theta"], s["expert_width"], s["num_experts"],
            s["experts_held"], s["expert_offset"], s["top_k"], s["eps"]) == (
        2304, 32, 4, 128, 1024, 500000.0, 896, 64, 16, 0, 8, 1e-6)
    assert s["yarn"] == dict(
        theta=500000.0, factor=16.0, original=8192, beta_fast=32.0,
        beta_slow=1.0, attention_factor=1.2772588722239782)
    model = cell["family"].build_model(cell["config"]).cfg
    from geomx_tpu.ops.gqa_elementwise import Yarn
    assert model.yarn == Yarn(**s["yarn"])
    assert model.expert_form == {"scoring": "softmax"}
    assert (model.shared_experts, model.post_norms, model.routed_scaling,
            model.embedding_scale) == (0, False, 1.0, 1.0)
    # a configuration the family cannot run is refused, not bent
    bad = json.loads(json.dumps(cell["config"]))
    bad["norm_topk_prob"] = False
    with pytest.raises(ValueError, match="renormalised"):
        cell["family"].sizes(bad)


def test_the_cell_trains_at_the_rate_the_issue_names():
    cell = real_cell()
    assert cell["config"]["optimizer"] == {
        "name": "adam", "lr": 1e-5, "b1": 0.9, "b2": 0.999, "eps": 1e-8}
    family = cell["family"]
    for name, shape in (("router_kernel", (2304, 64)),
                        ("experts_gate_kernel", (16, 2304, 896)),
                        ("experts_down_kernel", (16, 896, 2304))):
        assert family.weight_std(("layer2", "ffn", "core", name), shape) == \
            pytest.approx(shape[-2] ** -0.5), name
    assert family.weight_std(("layer4", "mixer", "core", "out_kernel"),
                             (4096, 2304)) == pytest.approx(4096 ** -0.5)
    assert family.weight_std(("embedding",), (24576, 2304)) == \
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
    assert count(shapes) == stated["total"] == 595_154_176
    assert stated["total"] == (4 * stated["expert_layer"]
                               + stated["embedding_plus_head"]
                               + stated["final_norm"])
    assert stated["expert_layer"] == 120_476_416 == (
        stated["attention"] + stated["block_norms"] + stated["router"]
        + 16 * stated["routed_expert"])
    assert stated["attention"] == 21_233_920 == (
        2 * 2304 * 4096 + 2 * 2304 * 512 + 256)
    assert stated["routed_expert"] == 3 * 2304 * 896 == 6_193_152
    assert stated["router"] == 2304 * 64
    assert stated["embedding_plus_head"] == 2 * 24576 * 2304
    for i in range(1, 5):
        assert count(shapes[f"layer{i}"]) == stated["expert_layer"], i
        core = shapes[f"layer{i}"]["mixer"]["core"]
        assert count(core) == stated["attention"]
        # no gate, no post-norms, no shared expert
        assert sorted(core) == ["k_kernel", "k_norm", "out_kernel",
                                "q_kernel", "q_norm", "v_kernel"]
        assert sorted(shapes[f"layer{i}"]["mixer"]) == ["core", "norm"]
        assert sorted(shapes[f"layer{i}"]["ffn"]) == ["core", "norm"]
        assert sorted(shapes[f"layer{i}"]["ffn"]["core"]) == [
            "experts_down_kernel", "experts_gate_kernel",
            "experts_up_kernel", "router_kernel"]
    assert shapes["layer4"]["mixer"]["core"]["k_kernel"].shape == (2304, 512)
    assert shapes["layer1"]["ffn"]["core"]["experts_up_kernel"].shape == (
        16, 2304, 896)
    names = [p[-1].key for p, _ in
             jax.tree_util.tree_flatten_with_path(shapes)[0]]
    assert names.count("scale") == 4 * (2 + 2) + 1


def test_flops_and_pairs_from_shapes():
    cell = real_cell()
    family, config = cell["family"], cell["config"]
    assert family.seen_pairs(16384, 1024) == 16_253_440
    assert family.seen_pairs(16384, None) == 134_225_920
    window = family.window_attention_shape(config)
    assert window == {"batch": 1, "heads": 32, "kv_heads": 4,
                      "length": 16384, "qk_dim": 128, "v_dim": 128,
                      "pairs": 16_253_440, "layers": 3}
    assert family.window_attention_flops_per_step(window) == (
        1536 * 16_253_440 * 32 * 3)
    full = family.global_attention_shape(config)
    assert (full["pairs"], full["layers"]) == (134_225_920, 1)
    assert family.global_attention_flops_per_step(full) == (
        1536 * 134_225_920 * 32)
    # 12.2 and 33.5 ms a step at the bf16 peak: the one full layer at 16k
    # is four times a global layer's pairs a sequence of the other cells
    assert family.window_attention_flops_per_step(window) / 197e12 == \
        pytest.approx(12.17e-3, rel=1e-3)
    assert family.global_attention_flops_per_step(full) / 197e12 == \
        pytest.approx(33.49e-3, rel=1e-3)
    per_token = family.forward_flops_per_token(config)
    assert family.train_flops_per_sample(config) == 3 * 16384 * per_token
    # outside the cores: 4 layers' four projections, the router over 64
    # and 2 held picks a token (16 x 8 / 64) with no shared expert; the head
    outside = (4 * (2 * 2304 * (2 * 4096 + 2 * 512) + 2 * 2304 * 64
                    + 2 * 6 * 2304 * 896) + 2 * 2304 * 24576)
    cores = 512 * 32 * (3 * 16_253_440 + 134_225_920) / 16384
    assert per_token == pytest.approx(outside + cores, rel=1e-12)
    # 27.8 TFLOP a step (ISSUE 40)
    assert family.train_flops_per_sample(config) == pytest.approx(
        27.84e12, rel=1e-3)
    for name in ("attention_shape", "latent_attention_shape",
                 "kda_scan_shape", "ssd_scan_shape"):
        assert not hasattr(family, name), name


def test_data_is_tokens_of_the_slice_with_the_next_token_as_label():
    cell = real_cell()
    x, y = cell["family"].make_data(cell["config"],
                                    np.random.default_rng(2 ** 31 + 5), 3)
    assert x.shape == y.shape == (3, 16384) and x.dtype == np.int32
    assert 0 <= x.min() and x.max() < 24576
    assert np.array_equal(x[:, 1:], y[:, :-1])


def test_the_cells_files_say_where_each_limit_comes_from():
    cell = real_cell()
    workload = cell["workload"]
    # four steps a segment: the host's work between segments is hidden
    # behind queued steps three times in four (the file says why)
    assert (workload["log_every"], workload["trace_segments"]) == (4, 2)
    assert "idle" in workload["segments_from"]
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
    assert "control" in workload["limits"]["first_grad_error"]["from"]
    assert workload["limits"]["first_grad_error"]["limit"] < 0.5
    entry = Registry(ROOT).workloads[CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "fsa-dense-1x1", 1)


def rehearse(name, seed):
    return run.run_cell(registry(), name, seed, 30.0, False,
                        rehearse_segments=3)


def test_the_whole_tiny_decoder_through_fit_meets_the_reference(capsys):
    """float32 program: `Trainer.fit` (loader, the model's own loss, FSA's
    dense tier, Adam) against `reference_steps` on the plain reference, to
    rounding, over three steps."""
    result = rehearse("tiny-mellum-f32", 2 ** 31 + 77)
    out = capsys.readouterr().out
    assert result["correct"] is True, out
    checks = result["checks"]
    assert checks["first_grad_error"]["value"] < 1e-4
    assert checks["loss_gap"]["value"] < 1e-5
    assert checks["delta_gap"]["value"] < 1e-3
    # the expert layers' counters came through the window's LoopStats
    stats = json.loads([line for line in out.splitlines()
                        if line.startswith("LOOP_STATS ")][0][11:])
    assert stats["counters"]["moe/dropped"]["total"] == 0.0
    assert stats["counters"]["moe/assignments_mean"]["count"] == 3


def test_the_bfloat16_program_is_sound_and_the_float8_control_is_not():
    """The tiny cell at bfloat16: limits can sit between the program's
    readings and the control's (the plain reference at float8 in the
    program's place), as the chip cell's do at its own size."""
    from benchmark.references.numerics import next_lower
    cell = registry().cell("tiny-mellum-f32")
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


@functools.lru_cache(maxsize=None)
def tiny_faults():
    from benchmark.tools import planted_faults
    return planted_faults.read_faults(
        registry().cell("tiny-mellum-f32"), 2 ** 31 + 77)


@pytest.mark.parametrize("fault, number, least, most", [
    ("gradient_scaled_by_half", "first_grad_gap", 0.5, 0.5),
    ("gradient_scaled_by_half", "first_grad_error", 0.5, 0.5),
    ("one_leaf_missing", "first_grad_gap", 1.0, 1.0),
    ("state_unchanged", "delta_gap", 1.0, 1.0),
    ("half_the_batch_left_out", "first_grad_error", 0.3, 2.0),
])
def test_a_planted_fault_reads_what_the_limits_are_set_against(
        fault, number, least, most):
    """`benchmark/tools/planted_faults.py` on the tiny cell (two rows a
    slot; the chip cell's one row has no half to leave out, and its three
    other faults are planted in the reference's own readings): each fault
    moves the number that is there to catch it, and fails the limits."""
    numbers = tiny_faults()[fault]
    assert least - 1e-6 <= numbers[number] <= most + 1e-6, numbers
    limits = registry().cell("tiny-mellum-f32")["workload"]["limits"]
    assert check.verdict(numbers, limits)[0] is False


def empty_context(cell):
    """No trace, no table, no counters (the parent's program)."""
    return {"cell": cell, "trace": None,
            "loop_stats": {"steps": 4, "wall_s": 1.0, "phases": {}},
            "step_layers": None, "peaks": PEAKS}


@pytest.mark.parametrize("name", EXPERT_READERS + ATTENTION_READERS)
def test_readers_apply_where_listed_and_read_nothing_from_nothing(name):
    reg = Registry(ROOT)
    reader = {m.NAME: m for m in reg.layer_metrics()}[name]
    entry = [m for m in SPEC["per_layer"] if m["name"] == name][0]
    assert CELL in entry["workloads"]
    assert reader.applies(reg.cell(CELL))
    assert entry["moves"] == "samples_per_s_chip"
    assert entry["unit"] == reader.UNIT
    assert reader.read(empty_context(reg.cell(CELL))) is None


def has_shared_expert(config: dict) -> bool:
    return any("shared_expert" in key and isinstance(value, int) and value > 0
               for key, value in config.items())


@pytest.mark.parametrize("cell", CELLS)
def test_every_listed_cell_is_one_its_reader_applies_to(cell):
    """Over whatever cells the file lists: a per-layer metric names a cell
    in its `workloads` where its reader applies to it and finds something
    to read: everywhere it applies, but for the shared expert's time in a
    configuration that has no shared expert."""
    reg = Registry(ROOT)
    readers = {m.NAME: m for m in reg.layer_metrics()}
    loaded = reg.cell(cell)
    for entry in SPEC["per_layer"]:
        listed = cell in entry.get("workloads", CELLS)
        reads = readers[entry["name"]].applies(loaded) and (
            entry["name"] not in SILENT_READERS
            or has_shared_expert(loaded["config"]))
        assert reads == listed, entry["name"]


@pytest.mark.parametrize("name", SILENT_READERS)
def test_a_reader_with_nothing_to_read_here_does_not_list_the_cell(name):
    reg = Registry(ROOT)
    cell = reg.cell(CELL)
    reader = {m.NAME: m for m in reg.layer_metrics()}[name]
    entry = [m for m in SPEC["per_layer"] if m["name"] == name][0]
    assert reader.applies(cell) and not has_shared_expert(cell["config"])
    assert CELL not in entry["workloads"]
    assert reader.read(empty_context(cell)) is None


def test_other_families_readers_do_not_apply():
    reg = Registry(ROOT)
    cell = reg.cell(CELL)
    applying = {m.NAME for m in reg.layer_metrics() if m.applies(cell)}
    always = {m["name"] for m in SPEC["per_layer"] if "workloads" not in m}
    assert applying == always | set(
        EXPERT_READERS + SILENT_READERS + ATTENTION_READERS)


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
        "fusion.6": OpLayer(fb + "lm/loss", "step program", "forward"),
        "fusion.7": OpLayer(fb + "moe/route", "step program", "backward"),
        "fusion.8": OpLayer(fb + "moe/experts/moe/dispatch", "step program",
                            "forward")}
    ctx = {"cell": reg.cell(CELL), "step_layers": table, "peaks": PEAKS,
           "trace": {"steps": 2, "by_op_s": {
               "custom.1": 0.05, "custom.2": 0.15, "custom.3": 0.1,
               "fusion.4": 0.08, "fusion.5": 0.02, "fusion.6": 0.04,
               "fusion.7": 0.006, "fusion.8": 0.01, "not.in.table": 9.0}}}
    assert readers["window_attn_ms"].read(ctx) == pytest.approx(100.0)
    assert readers["gqa_proj_ms"].read(ctx) == pytest.approx(40.0)
    assert readers["moe_experts_ms"].read(ctx) == pytest.approx(15.0)
    assert readers["moe_dispatch_ms"].read(ctx) == pytest.approx(5.0)
    assert readers["moe_route_ms"].read(ctx) == pytest.approx(3.0)
    assert readers["lm_loss_ms"].read(ctx) == pytest.approx(20.0)
    # no `moe/shared` scope in this program: the reader finds nothing, the
    # result line leaves the metric out and the cell is not on its list
    assert readers["moe_shared_ms"].read(ctx) is None
    # 2.40 TFLOP / 197 TFLOP/s = 12.17 ms of 100; 6.60 / 197 = 33.49 of 50
    assert readers["window_attn_roofline_pct"].read(ctx) == pytest.approx(
        12.166, rel=1e-3)
    assert readers["global_attn_roofline_pct"].read(ctx) == pytest.approx(
        100 * 33.490 / 50.0, rel=1e-3)
    ctx["loop_stats"] = {"steps": 8, "counters": {
        "moe/dropped": {"count": 4, "total": 0.0, "last": 0.0, "max": 0.0},
        "moe/assignments_mean": {"count": 4, "total": 8192.0, "last": 2048.0,
                                 "max": 2048.0}}}
    assert readers["moe_dropped_pct"].read(ctx) == 0.0
    # 4 steps x 4 layers x 16 held x 2,048 arrived; 1% of them dropped
    ctx["loop_stats"]["counters"]["moe/dropped"]["total"] = 5242.88
    assert readers["moe_dropped_pct"].read(ctx) == pytest.approx(1.0)


def test_a_roofline_share_cannot_pass_100_at_the_floor_itself():
    from geomx_tpu.telemetry.layers import OpLayer
    reg = Registry(ROOT)
    readers = {m.NAME: m for m in reg.layer_metrics()}
    cell = reg.cell(CELL)
    family, config = cell["family"], cell["config"]
    for name, scope, flops in (
            ("window_attn_roofline_pct", "gqa/window",
             family.window_attention_flops_per_step(
                 family.window_attention_shape(config))),
            ("global_attn_roofline_pct", "gqa/global",
             family.global_attention_flops_per_step(
                 family.global_attention_shape(config)))):
        ctx = {"cell": cell, "peaks": PEAKS, "step_layers": {
            "k": OpLayer("step/forward_backward/" + scope, "kernels",
                         "forward")},
            "trace": {"steps": 1, "by_op_s": {"k": flops / 197e12}}}
        assert readers[name].read(ctx) == pytest.approx(100.0)


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
    # twice what even routing sends the 16 held: 2 picks a token
    assert model.expert_pool == 2 * 16384 * 8 * 16 // 64 == 65536
    assert model.expert_pool % model.expert_rows == 0
    config["program"] = dict(program, loss_block_tokens=512,
                             expert_block_rows=128, expert_pool_places=4096,
                             remat_each_layer=False)
    other = cell["family"].build_model(config).cfg
    assert (other.loss_block, other.expert_rows, other.expert_pool,
            other.remat) == (512, 128, 4096, False)
    assert (model.window, model.num_kv_heads, model.top_k, model.num_experts,
            model.experts_held) == (1024, 4, 8, 64, 16)
