"""The `ouro` family and its cell: the configuration file against the
catalog row it was cut from, the parameter table counted from the built
model, the FLOP counts from shapes with the stack and the head counted
`total_ut_steps` times, the whole tiny looped decoder through
`Trainer.fit` against `reference_steps` under the harness, the float8
control, the three planted faults of the loop (a step fewer, the entropy
term left out, the wrong last mass), the four readers this family brought
and the accepted one that applies to the cell, and the `program` keys.
Whatever cells `BENCHMARK.json` lists are taken from the file: no set of
cell names and no position in `per_layer` is written here."""
import functools
import json
import os

import numpy as np
import pytest

from bench_paths import ROOT, TINY

from benchmark import check, run
from benchmark.cells import Registry

OURO = os.path.join(ROOT, "tests", "benchmark", "data_ouro")

# `config` of the catalog's row `Ouro-2.6B` (model-configs guide,
# architectures.jsonl), copied whole
CATALOG = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16, "num_hidden_layers": 48,
    "num_key_value_heads": 16, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "total_ut_steps": 4,
    "early_exit_threshold": 1, "use_sliding_window": False,
    "vocab_size": 49152}
REDUCED = ["num_hidden_layers"]
HELD = {"num_hidden_layers": 6}
CELL = "ouro26b-fsa-1c"
CONFIG = "ouro-2.6b-6of48"
TINY_CELL = "tiny-ouro-f32"
# the accepted reader whose `applies` takes this family (it has
# `layer_kinds`) and which finds something to read in its step
ACCEPTED_READERS = ["lm_loss_ms"]
NEW_READERS = ["dense_mlp_ms", "loop_exit_ms", "full_attn_roofline_pct",
               "attn_proj_ms"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
SPEC = Registry(ROOT).spec
CELLS = [w["name"] for w in SPEC["workloads"]]


def registry():
    return Registry(ROOT, extra=[OURO, TINY])


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
    assert config["kept_layers"] == [0, 1, 2, 3, 4, 5]
    assert "pipeline stages" in config["deployment"]
    assert "no layer is shared" in config["deployment"]
    assert (config["sequence_length"], config["per_chip_batch"],
            config["precision"], config["data_steps"]) == (
        8192, 1, "bfloat16", 16)
    # depth only: six full-attention layers with a dense MLP each, the loop
    # count, every head and the whole vocabulary as published
    assert family.layer_kinds(config) == (("global", "mlp"),) * 6
    assert config["total_ut_steps"] == 4 and config["exit_beta"] == 0.05
    # what config.json does not give is said to be assumed, one line each
    for key in ("block", "attention", "loop", "exit_gate", "objective"):
        assert "not in config.json" in config["assumed"][key], key
    for key in ("mlp", "exit_beta", "sequences", "weights", "what_it_is"):
        assert config["assumed"][key], key
    assert "2510.25741" in config["assumed"]["block"]
    assert "0.05" in config["assumed"]["exit_beta"]
    assert "not the 2.6B model" in config["assumed"]["what_it_is"]
    entry = [c for c in SPEC["configs"] if c["name"] == CONFIG][0]
    assert entry["reduced"] == REDUCED
    assert entry["source"] == config["source"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    path = os.path.join("/opt/skills/guides/model-configs",
                        "architectures.jsonl")
    if os.path.exists(path):        # the literal above is the row's own
        with open(path) as f:
            rows = [json.loads(line) for line in f]
        row = [r for r in rows if r["name"] == "Ouro-2.6B"]
        assert row[0]["config"] == CATALOG
        assert row[0]["source_url"] == config["source"]


def test_no_width_is_reduced():
    config = real_cell()["config"]
    for key in ("hidden_size", "intermediate_size", "head_dim",
                "num_attention_heads", "num_key_value_heads", "vocab_size",
                "total_ut_steps"):
        assert config[key] == CATALOG[key] and key not in REDUCED, key


def test_sizes_are_the_configurations_keys():
    cell = real_cell()
    s = cell["family"].sizes(cell["config"])
    assert (s["vocab"], s["hidden"], s["num_heads"], s["num_kv_heads"],
            s["head_dim"], s["rope_theta"], s["dense_width"], s["loops"],
            s["exit_beta"], s["eps"]) == (
        49152, 2048, 16, 16, 128, 1e6, 5632, 4, 0.05, 1e-6)
    model = cell["family"].build_model(cell["config"]).cfg
    assert (model.post_norms, model.embedding_scale, model.loops,
            model.exit_beta) == (True, 1.0, 4, 0.05)
    mixer = model.make_mixer("global", None)
    assert (mixer.qk_norm, mixer.gated, mixer.rope, mixer.window) == (
        False, False, 1e6, None)
    # a configuration the family cannot run is refused, not bent
    for key, value, said in (("rope_scaling", {"type": "yarn"}, "rotary"),
                             ("use_sliding_window", True, "window"),
                             ("sliding_window", 4096, "window"),
                             ("tie_word_embeddings", True, "untied")):
        bad = json.loads(json.dumps(cell["config"]))
        bad[key] = value
        with pytest.raises(ValueError, match=said):
            cell["family"].sizes(bad)
    bad = json.loads(json.dumps(cell["config"]))
    bad["layer_types"][3] = "sliding_attention"
    with pytest.raises(KeyError):
        cell["family"].layer_kinds(bad)


def test_the_cell_trains_at_the_rate_and_seeding_the_file_names():
    cell = real_cell()
    assert cell["config"]["optimizer"] == {
        "name": "adam", "lr": 1e-5, "b1": 0.9, "b2": 0.999, "eps": 1e-8}
    family = cell["family"]
    for path, shape in ((("layer2", "ffn", "core", "down_kernel"),
                         (5632, 2048)),
                        (("layer1", "mixer", "core", "q_kernel"),
                         (2048, 2048)),
                        (("head_kernel",), (2048, 49152)),
                        (("exit_gate", "kernel"), (2048, 1))):
        assert family.weight_std(path, shape) == pytest.approx(
            shape[-2] ** -0.5), path
    assert family.weight_std(("exit_gate", "bias"), (1,)) == 0.0
    assert family.weight_std(("embedding",), (49152, 2048)) == \
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
    assert count(shapes) == stated["total"] == 509_661_185
    assert stated["total"] == (
        6 * stated["layer"] + stated["embedding_plus_head"]
        + stated["final_norm"] + stated["exit_gate"])
    assert stated["attention"] == 16_777_216 == 4 * 2048 * 2048
    assert stated["mlp"] == 34_603_008 == 3 * 2048 * 5632
    assert stated["layer"] == 51_388_416 == (
        stated["attention"] + stated["mlp"] + stated["block_norms"])
    assert stated["embedding_plus_head"] == 2 * 49152 * 2048
    assert stated["exit_gate"] == 2048 + 1 == count(shapes["exit_gate"])
    # one stack's leaves whatever the loop count
    assert sorted(shapes) == ["embedding", "exit_gate", "final_norm",
                              "head_kernel"] + [
        f"layer{i}" for i in range(1, 7)]
    for i in range(1, 7):
        block = shapes[f"layer{i}"]
        assert count(block) == stated["layer"], i
        assert sorted(block["mixer"]["core"]) == [
            "k_kernel", "out_kernel", "q_kernel", "v_kernel"]   # no q/k norm
        assert sorted(block["mixer"]) == sorted(block["ffn"]) == [
            "core", "norm", "post_norm"]        # sandwich norms
    assert shapes["exit_gate"]["kernel"].shape == (2048, 1)
    # 8.15 GB at the program's 16 B a parameter; 2.04 GB a dense sync
    assert 16 * stated["total"] == pytest.approx(8.15e9, rel=1e-3)
    assert 4 * stated["total"] == 2_038_644_740


def test_flops_from_shapes_count_four_passes():
    cell = real_cell()
    family, config = cell["family"], cell["config"]
    shape = family.global_attention_shape(config)
    assert shape == {"batch": 1, "heads": 16, "kv_heads": 16, "length": 8192,
                     "qk_dim": 128, "v_dim": 128,
                     "pairs": 8192 * 8193 // 2, "layers": 24}
    cores = family.global_attention_flops_per_step(shape)
    assert cores == 1536 * 16 * (8192 * 8193 // 2) * 24
    per_token = family.forward_flops_per_token(config)
    assert family.train_flops_per_sample(config) == 3 * 8192 * per_token
    application = 2 * 2048 * 4 * 2048 + 6 * 2048 * 5632
    core = 4 * 128 * 16 * 8193 / 2
    head = 2 * 2048 * 49152
    want = 4 * (6 * (application + core) + head + 2 * 2048)
    assert per_token == pytest.approx(want, rel=1e-12)
    assert application == pytest.approx(102.76e6, rel=1e-4)
    assert per_token == pytest.approx(4.077e9, rel=1e-3)
    # 100.2 TFLOP a step: dense products 60%, cores 20%, head passes 20%
    total = family.train_flops_per_sample(config)
    assert total == pytest.approx(100.2e12, rel=1e-3)
    assert cores / total == pytest.approx(0.20, abs=5e-3)
    assert 3 * 8192 * 24 * application / total == pytest.approx(0.605, abs=5e-3)
    assert 3 * 8192 * 4 * head / total == pytest.approx(0.1975, abs=5e-3)
    # one loop step is a quarter of it: the factor is the configuration's
    once = json.loads(json.dumps(config))
    once["total_ut_steps"] = 1
    assert family.forward_flops_per_token(once) == pytest.approx(
        per_token / 4, rel=1e-12)
    assert family.global_attention_shape(once)["layers"] == 6
    for name in ("attention_shape", "window_attention_shape",
                 "latent_attention_shape", "kda_scan_shape",
                 "ssd_scan_shape"):
        assert not hasattr(family, name), name


def test_data_is_tokens_of_the_vocabulary_with_the_next_token_as_label():
    cell = real_cell()
    x, y = cell["family"].make_data(cell["config"],
                                    np.random.default_rng(2 ** 31 + 5), 3)
    assert x.shape == y.shape == (3, 8192) and x.dtype == np.int32
    assert 0 <= x.min() and 49000 < x.max() < 49152
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
    assert "TO BE SET" not in json.dumps(cell["config"])
    for name in ("loss_gap", "first_grad_gap", "delta_gap",
                 "first_grad_error"):
        said = workload["limits"][name]["from"]
        assert "sound" in said and "seeds" in said, name
        assert "control" in said or "planted" in said, name
    for name in ("loss_gap", "first_grad_gap", "first_grad_error"):
        assert "loop step fewer" in workload["limits"][name]["from"], name
    assert "control" in workload["limits"]["first_grad_error"]["from"]
    entry = Registry(ROOT).workloads[CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "fsa-dense-1x1", 1)
    assert "8,192" in entry["why"] and "60%" in entry["why"]
    assert "4 x the FLOPs of its 2.04 GB of gradient" in entry["why"]


def test_the_benchmark_has_no_cell_on_four_chips():
    assert all(w["chips"] == 1 for w in SPEC["workloads"])
    assert len(SPEC["workloads"]) <= 24


def rehearse(name, seed):
    return run.run_cell(registry(), name, seed, 30.0, False,
                        rehearse_segments=3)


def test_the_whole_tiny_decoder_through_fit_meets_the_reference(capsys):
    """float32 program: `Trainer.fit` (loader, the model's own expected-
    exit loss, FSA's dense tier, Adam) against `reference_steps` on the
    plain reference, to rounding, over three steps."""
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
    for t in (1, 2, 3, 4):
        assert counters[f"loop/loss_{t}"]["count"] == 3
        assert 0 < counters[f"loop/exit_mass_{t}"]["last"] < 1
    assert counters["loop/exit_entropy"]["last"] > 0
    assert counters["lm/main_loss"]["count"] == 3


@functools.lru_cache(maxsize=None)
def tiny_readings():
    """The tiny cell at bfloat16: the program's readings and the float8
    control's, each against the float32 reference."""
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
    return {name: check.compare(side, reference, 0) for name, side in (
        ("sound", program), ("control", lower))}


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


@functools.lru_cache(maxsize=None)
def tiny_faults():
    from benchmark.tools import loop_left_out
    return loop_left_out.read_faults(registry().cell(TINY_CELL), 2 ** 31 + 77)


@pytest.mark.parametrize("fault, number, least, most", [
    ("a_loop_step_fewer", "loss_gap", 0.001, 0.1),
    ("a_loop_step_fewer", "first_grad_error", 0.1, 2.0),
    ("entropy_term_left_out", "loss_gap", 0.005, 0.05),
    ("entropy_term_left_out", "first_grad_gap", 0.01, 2.0),
    ("entropy_term_left_out", "first_grad_error", 0.01, 1.0),
    ("wrong_last_mass", "loss_gap", 0.02, 0.5),
    ("wrong_last_mass", "first_grad_gap", 0.5, 100.0),
    ("wrong_last_mass", "first_grad_error", 0.5, 100.0),
    ("gradient_scaled_by_half", "first_grad_gap", 0.5, 0.5),
    ("gradient_scaled_by_half", "first_grad_error", 0.5, 0.5),
    ("one_leaf_missing", "first_grad_gap", 1.0, 1.0),
    ("state_unchanged", "delta_gap", 1.0, 1.0),
])
def test_a_planted_fault_reads_what_the_limits_are_set_against(
        fault, number, least, most):
    """`benchmark/tools/loop_left_out.py` on the tiny cell: a loop step
    fewer, the entropy term left out, the last step given lambda^T's share
    and not the rest of the mass, and the three faults
    `planted_faults.planted()` plants in the reference's own readings;
    each moves the number that is there to catch it, and fails the
    limits."""
    numbers = tiny_faults()[fault]
    assert least - 1e-6 <= numbers[number] <= most + 1e-6, numbers
    limits = registry().cell(TINY_CELL)["workload"]["limits"]
    assert check.verdict(numbers, limits)[0] is False


@pytest.mark.parametrize("fault", ["a_loop_step_fewer",
                                   "entropy_term_left_out",
                                   "wrong_last_mass"])
def test_each_loop_fault_fails_the_loss_and_a_gradient_limit(fault):
    limits = registry().cell(TINY_CELL)["workload"]["limits"]
    failed = {line["number"] for line in check.verdict(
        tiny_faults()[fault], limits)[1] if not line["ok"]}
    assert "loss_gap" in failed
    assert failed & {"first_grad_gap", "first_grad_error"}


def test_the_tool_refuses_a_cell_with_no_loop():
    from benchmark.tools import loop_left_out
    with pytest.raises(SystemExit, match="no loop"):
        loop_left_out.read_faults(Registry(ROOT).cell("kimilinear-fsa-1c"), 1)


def empty_context(cell):
    """No trace, no table, no counters (the parent's program)."""
    return {"cell": cell, "trace": None,
            "loop_stats": {"steps": 4, "wall_s": 1.0, "phases": {}},
            "step_layers": None, "peaks": PEAKS}


@pytest.mark.parametrize("name", ACCEPTED_READERS + NEW_READERS)
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
    """Over whatever cells the file lists: `dense_mlp_ms` where every
    feed-forward half of the family's layers is dense, `loop_exit_ms` where the stack runs
    more than once, `full_attn_roofline_pct` and `attn_proj_ms` where the
    family has a global attention shape, no window one, and full attention
    in every layer."""
    reg = Registry(ROOT)
    loaded = reg.cell(cell)
    family, config = loaded["family"], loaded["config"]
    entry = [m for m in SPEC["per_layer"] if m["name"] == name][0]
    assert (entry["source"], entry["moves"]) == (
        "device_trace", "samples_per_s_chip")
    assert (entry["layer"], entry["better"]) == {
        "full_attn_roofline_pct": ("kernels", "higher")}.get(
        name, ("step program", "lower"))
    full_only = (hasattr(family, "global_attention_shape")
                 and not hasattr(family, "window_attention_shape")
                 and all(m == "global" for m, _ in family.layer_kinds(config)))
    want = {"dense_mlp_ms": hasattr(family, "layer_kinds") and all(
                ffn == "mlp" for _, ffn in family.layer_kinds(config)),
            "loop_exit_ms": config.get("total_ut_steps", 1) > 1,
            "full_attn_roofline_pct": full_only,
            "attn_proj_ms": full_only}[name]
    assert readers()[name].applies(loaded) == want
    assert (cell in entry["workloads"]) == want


def test_other_families_readers_do_not_apply():
    reg = Registry(ROOT)
    cell = reg.cell(CELL)
    applying = {m.NAME for m in reg.layer_metrics() if m.applies(cell)}
    always = {m["name"] for m in SPEC["per_layer"] if "workloads" not in m}
    assert applying == always | set(ACCEPTED_READERS + NEW_READERS)


def test_scope_readers_join_the_trace_with_the_programs_table():
    """The four new readers by hand on a made table; the head passes count
    under `lm/loss`, not under the gate's scope."""
    from geomx_tpu.telemetry.layers import OpLayer
    reg = Registry(ROOT)
    fb = "step/forward_backward/"
    table = {
        "fusion.1": OpLayer(fb + "gqa/proj", "step program", "forward"),
        "fusion.2": OpLayer(fb + "gqa/proj", "step program", "backward"),
        "custom.3": OpLayer(fb + "gqa/global/attn/core", "kernels",
                            "backward"),
        "custom.4": OpLayer(fb + "gqa/global/attn/core", "kernels",
                            "forward"),
        "fusion.5": OpLayer(fb + "ffn/mlp", "step program", "forward"),
        "fusion.6": OpLayer(fb + "ffn/mlp", "step program", "backward"),
        "fusion.7": OpLayer(fb + "loop/exit", "step program", "forward"),
        "fusion.8": OpLayer(fb + "loop/exit", "step program", "backward"),
        "fusion.9": OpLayer(fb + "lm/loss", "step program", "forward"),
        "fusion.10": OpLayer(fb, "step program", "forward")}
    ctx = {"cell": reg.cell(CELL), "step_layers": table, "peaks": PEAKS,
           "trace": {"steps": 2, "by_op_s": {
               "fusion.1": 0.04, "fusion.2": 0.06, "custom.3": 0.3,
               "custom.4": 0.1, "fusion.5": 0.2, "fusion.6": 0.5,
               "fusion.7": 0.001, "fusion.8": 0.003, "fusion.9": 0.3,
               "fusion.10": 0.02, "not.in.table": 9.0}}}
    got = {name: readers()[name].read(ctx) for name in (
        "dense_mlp_ms", "loop_exit_ms", "attn_proj_ms", "lm_loss_ms")}
    assert got["dense_mlp_ms"] == pytest.approx(1e3 * 0.7 / 2)
    assert got["loop_exit_ms"] == pytest.approx(1e3 * 0.004 / 2)
    assert got["attn_proj_ms"] == pytest.approx(1e3 * 0.1 / 2)
    assert got["lm_loss_ms"] == pytest.approx(1e3 * 0.3 / 2)
    # 19.79 TFLOP / 197 TFLOP/s = 100.5 ms of the 200 under gqa/global
    flops = 1536 * 16 * (8192 * 8193 // 2) * 24
    assert readers()["full_attn_roofline_pct"].read(ctx) == pytest.approx(
        100 * (flops / 197e12) / 0.2, rel=1e-9)
    assert readers()["full_attn_roofline_pct"].read(ctx) == pytest.approx(
        50.2, abs=0.1)
    # a program with none of the new scopes (the parent): nothing to read
    for name in list(table):
        if "ffn/mlp" in table[name].scope or "loop/" in table[name].scope:
            del table[name]
    assert readers()["dense_mlp_ms"].read(ctx) is None
    assert readers()["loop_exit_ms"].read(ctx) is None
    assert readers()["attn_proj_ms"].read(ctx) == pytest.approx(50.0)


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
    flops = family.global_attention_flops_per_step(
        family.global_attention_shape(config))
    ctx = {"cell": cell, "peaks": PEAKS, "step_layers": {
        "k": OpLayer("step/forward_backward/gqa/global", "kernels",
                     "forward")},
        "trace": {"steps": 1, "by_op_s": {"k": flops / 197e12}}}
    assert readers()["full_attn_roofline_pct"].read(ctx) == pytest.approx(
        100.0)


def test_every_program_key_of_the_file_reaches_the_model():
    """`program` records what was chosen to make the cell fit; a key that
    `build_model` did not read would drift from the code in silence."""
    cell = real_cell()
    config = json.loads(json.dumps(cell["config"]))
    model = cell["family"].build_model(config).cfg
    program = config["program"]
    assert (model.loss_block, model.remat) == (
        program["loss_block_tokens"], program["remat_each_layer"])
    assert set(program) == {"loss_block_tokens", "remat_each_layer", "note"}
    config["program"] = dict(program, loss_block_tokens=512,
                             remat_each_layer=False)
    other = cell["family"].build_model(config).cfg
    assert (other.loss_block, other.remat) == (512, False)
    assert (model.num_heads, model.num_kv_heads, model.loops) == (16, 16, 4)


def test_the_timing_tool_reads_the_new_files_keys():
    """`tools/flash_attention_timing.py ouro-full` takes its shape from
    the configuration's own keys."""
    import sys
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import flash_attention_timing as flash
    assert flash.NAMED["ouro-full"] == (1, 8192, 16, 128, 128, True, 16)
    assert flash.full_shape("benchmark/configs/mellum2-12b-ep4.json") == (
        1, 16384, 32, 128, 128, True, 4) == flash.NAMED["mellum-global"]
