"""The `nemotron_h` family and its cell: the configuration file against the
catalog row it was cut from, the parameter table counted from the built
model, the FLOP and byte counts from shapes, the whole tiny decoder through
`Trainer.fit` against `reference_steps` under the harness, the float8
control, the new per-layer readers on a program that lacks what they read,
and the `program` keys."""
import functools
import json
import os

import numpy as np
import pytest

from bench_paths import ROOT, TINY

from benchmark import check, run
from benchmark.cells import Registry

NEMOTRON = os.path.join(ROOT, "tests", "benchmark", "data_nemotron_h")

# `config` of the catalog's row `NVIDIA-Nemotron-3-Super-120B-A12B-BF16`
# (model-configs guide, architectures.jsonl), copied whole
CATALOG = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 4096,
    "hybrid_override_pattern": (
        "MEMEMEM*E" * 3 + "MEMEMEMEM*E" * 4 + "MEMEMEM*E" + "MEMEMEME"),
    "intermediate_size": 2688, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu",
    "mamba_num_heads": 128, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 2688, "moe_latent_size": 1024,
    "moe_shared_expert_intermediate_size": 5376,
    "moe_shared_expert_overlap": False, "mtp_hybrid_override_pattern": "*E",
    "n_group": 1, "n_groups": 8, "n_routed_experts": 512,
    "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 22,
    "num_hidden_layers": 88, "num_key_value_heads": 2,
    "num_logits_to_keep": 1, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072}
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size",
           "mamba_num_heads", "n_groups", "num_attention_heads",
           "num_key_value_heads", "num_nextn_predict_layers"]
HELD = {"num_hidden_layers": 11, "n_routed_experts": 8, "vocab_size": 16384,
        "mamba_num_heads": 16, "n_groups": 1, "num_attention_heads": 4,
        "num_key_value_heads": 1, "num_nextn_predict_layers": 0}
CELL = "nemotron3super-fsa-1c"
CONFIG = "nemotron3-super-tp8-ep64"
DECODERS = ["kimilinear-fsa-1c", "trinitymini-fsa-1c", CELL]
OWN_READERS = ["ssd_scan_ms", "ssd_scan_roofline_pct", "ssd_proj_ms",
               "moe_latent_ms"]
EXPERT_READERS = ["moe_shared_ms", "moe_route_ms"]
APPENDED_TO = ["moe_experts_ms", "moe_dispatch_ms", "moe_dropped_pct",
               "lm_loss_ms"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def registry():
    return Registry(ROOT, extra=[NEMOTRON, TINY])


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
    assert config["router_experts"] == 512 and config["expert_offset"] == 0
    assert config["num_experts"] == config["n_routed_experts"] == 8
    assert config["kept_layers"] == list(range(27, 38))
    pattern = config["hybrid_override_pattern"]
    assert "".join(pattern[i] for i in config["kept_layers"]) == "MEMEMEMEM*E"
    assert (pattern.count("M"), pattern.count("E"), pattern.count("*")) == (
        40, 40, 8)
    # an eighth of the heads and of the vocabulary, a 64th of the experts
    for key, over in (("mamba_num_heads", 8), ("n_groups", 8),
                      ("num_attention_heads", 8), ("vocab_size", 8),
                      ("n_routed_experts", 64)):
        assert config[key] * over == CATALOG[key], key
    # the query heads held read one key/value head: 32 on 2 is 16 a head
    assert config["num_attention_heads"] <= 32 // 2
    assert "64 chips" in config["deployment"]
    assert "No code stands in" in config["deployment"]
    assert (config["sequence_length"], config["per_chip_batch"],
            config["precision"], config["data_steps"]) == (
        8192, 2, "bfloat16", 16)
    m, a, e = ("mamba", None), ("attention", None), (None, "moe")
    assert family.layer_kinds(config) == (m, e, m, e, m, e, m, e, m, a, e)
    for key in ("single_half_layers", "mamba2", "decay_centres",
                "short_conv", "attention", "latent_moe", "moe", "sequences",
                "weights", "what_it_is"):
        assert config["assumed"][key], key
    assert "rope_theta" in config["assumed"]["attention"]
    assert "selection bias" in config["assumed"]["moe"]
    assert "time_step" in config["assumed"]["decay_centres"]
    assert "not guessed" in config["mtp_note"]
    entry = [c for c in Registry(ROOT).spec["configs"]
             if c["name"] == CONFIG][0]
    assert entry["reduced"] == REDUCED
    assert entry["source"] == config["source"]
    path = os.path.join("/opt/skills/guides/model-configs",
                        "architectures.jsonl")
    if os.path.exists(path):        # the literal above is the row's own
        with open(path) as f:
            rows = [json.loads(line) for line in f]
        row = [r for r in rows
               if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16"]
        assert row[0]["config"] == CATALOG
        assert row[0]["source_url"] == config["source"]


def test_no_width_is_reduced():
    config = real_cell()["config"]
    for key in ("hidden_size", "head_dim", "mamba_head_dim", "ssm_state_size",
                "moe_latent_size", "moe_intermediate_size",
                "moe_shared_expert_intermediate_size", "conv_kernel",
                "chunk_size", "num_experts_per_tok", "routed_scaling_factor",
                "expand"):
        assert config[key] == CATALOG[key] and key not in REDUCED, key


def test_the_cell_trains_at_the_rate_the_issue_names():
    cell = real_cell()
    assert cell["config"]["optimizer"] == {
        "name": "adam", "lr": 1e-5, "b1": 0.9, "b2": 0.999, "eps": 1e-8}
    family = cell["family"]
    core = ("layer1", "mixer", "core")
    assert family.weight_std(core + ("in_kernel",), (4096, 2320)) == \
        pytest.approx(4096 ** -0.5)
    assert family.weight_std(("layer2", "ffn", "core", "experts_up_kernel"),
                             (8, 1024, 2688)) == pytest.approx(1024 ** -0.5)
    # the seeding that keeps the seeded router even (assumed.residual_stream):
    # an embedding of sqrt(the published depth), every matrix at fan-in
    assert family.weight_std(("embedding",), (16384, 4096)) == \
        pytest.approx(88 ** 0.5)
    assert family.PUBLISHED_LAYERS == \
        cell["config"]["published"]["num_hidden_layers"] == 88
    assert cell["config"]["rescale_prenorm_residual"] is True
    for name, shape in (("shared_up_kernel", (4096, 5376)),
                        ("shared_down_kernel", (5376, 4096)),
                        ("latent_down_kernel", (4096, 1024)),
                        ("latent_up_kernel", (1024, 4096)),
                        ("router_kernel", (4096, 512)),
                        ("experts_down_kernel", (8, 2688, 1024))):
        assert family.weight_std(("layer2", "ffn", "core", name), shape) == \
            pytest.approx(shape[-2] ** -0.5), name
    assert family.weight_std(core + ("out_kernel",), (1024, 4096)) == \
        pytest.approx(1024 ** -0.5)
    # the leaves that are no matrix: the file's assumed.decay_centres
    assert [family.weight_std(core + (name,), (16,)) for name in
            ("A_log", "dt_bias", "D")] == [0.5, 1.0, 0.25]
    assert family.weight_std(core + ("conv_kernel",), (4, 1280)) == 0.5
    assert family.weight_std(core + ("conv_bias",), (1280,)) == 0.2
    # program and reference agree on the centres the offsets stand on
    from benchmark.references import nemotron_h as plain
    from geomx_tpu.models import nemotron_h as model
    for name in ("A_LOG_CENTRE", "DT_BIAS_CENTRE", "D_CENTRE"):
        assert getattr(plain, name) == getattr(model, name), name


def test_parameter_count_is_the_files_and_the_issues():
    import jax
    cell = real_cell()
    config = cell["config"]
    model = cell["family"].build_model(config)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           np.zeros((1, 128), np.int32)))["params"]
    count = lambda tree: sum(int(np.prod(s.shape))
                             for s in jax.tree.leaves(tree))
    stated = config["parameters"]
    assert count(shapes) == stated["total"] == 700_862_960
    assert stated["total"] == (
        5 * stated["mamba_layer"] + stated["attention_layer"]
        + 5 * stated["expert_layer"] + stated["embedding_plus_head"]
        + stated["final_norm"])
    assert stated["mamba_layer"] == 13_708_592 == (
        4096 + 4096 * 2320 + 1280 * 4 + 1280 + 3 * 16 + 1024 + 1024 * 4096)
    assert stated["attention_layer"] == 5_246_976
    assert stated["expert_layer_outside_routed"] == (
        4096 + stated["router"] + stated["latent_projections"]
        + stated["shared_expert"])
    assert stated["expert_layer"] == 98_570_240 == (
        stated["expert_layer_outside_routed"] + 8 * stated["routed_expert"])
    assert stated["embedding_plus_head"] + stated["final_norm"] == 134_221_824
    kinds = cell["family"].layer_kinds(config)
    for i, (mixer, ffn) in enumerate(kinds):
        want = stated["expert_layer"] if ffn else stated[mixer + "_layer"]
        assert count(shapes[f"layer{i + 1}"]) == want, i
    core = shapes["layer1"]["mixer"]["core"]
    assert core["in_kernel"].shape == (4096, 2320)
    assert core["conv_kernel"].shape == (4, 1280)
    assert core["out_norm"]["scale"].shape == (1024,)
    ffn = shapes["layer2"]["ffn"]["core"]
    assert ffn["router_kernel"].shape == (4096, 512)
    assert ffn["experts_up_kernel"].shape == (8, 1024, 2688)
    assert ffn["experts_down_kernel"].shape == (8, 2688, 1024)
    assert ffn["shared_up_kernel"].shape == (4096, 5376)
    assert "experts_gate_kernel" not in ffn and "shared_gate_kernel" not in ffn
    attention = shapes["layer10"]["mixer"]["core"]
    assert attention["q_kernel"].shape == (4096, 512)
    assert attention["k_kernel"].shape == (4096, 128)
    # one norm a layer, a gated norm a Mamba-2 layer, the final one; every
    # norm weight is a leaf named `scale` (weights.py makes ones)
    names = [p[-1].key for p, _ in
             jax.tree_util.tree_flatten_with_path(shapes)[0]]
    assert names.count("scale") == 11 + 5 + 1


def test_flops_and_bytes_from_shapes():
    cell = real_cell()
    family, config = cell["family"], cell["config"]
    per_token = family.forward_flops_per_token(config)
    assert family.train_flops_per_sample(config) == 3 * 8192 * per_token
    mamba = 2 * 4096 * 2320 + 2 * 1024 * 4096 + 4 * 16 * 64 * 128
    attention = 2 * 4096 * (2 * 512 + 2 * 128) + 4 * 128 * 4 * 8193 / 2
    moe = (2 * 4096 * 512 + 4 * 4096 * 1024 + 4 * 4096 * 5376
           + (8 * 22 / 512) * 4 * 1024 * 2688)
    assert per_token == pytest.approx(
        5 * mamba + attention + 5 * moe + 2 * 4096 * 16384, rel=1e-12)
    # 42 TFLOP a step (ISSUE 38), over half of it the shared experts'
    step = 2 * family.train_flops_per_sample(config)
    assert step == pytest.approx(42.0e12, rel=0.01)
    assert 3 * 16384 * 5 * 4 * 4096 * 5376 / step == pytest.approx(0.515,
                                                                   abs=0.01)
    shape = family.ssd_scan_shape(config)
    assert shape == {"tokens": 16384, "heads": 16, "head_dim": 64,
                     "groups": 1, "state": 128, "layers": 5}
    assert family.ssd_scan_flops_per_step(shape) == (
        12 * 64 * 128 * 16384 * 16 * 5)
    # forward X, B, C in, dt in, Y out; backward those and dY in, dX, dB,
    # dC, ddt out: 11,968 B a token and layer
    assert family.ssd_scan_bytes_per_step(shape) == 11_968 * 16384 * 5
    # the bytes bind: 1.20 ms against 0.65 ms a step on a v5e
    assert family.ssd_scan_bytes_per_step(shape) / 819e9 == pytest.approx(
        1.197e-3, rel=1e-3)
    assert family.ssd_scan_flops_per_step(shape) / 197e12 == pytest.approx(
        0.654e-3, rel=1e-3)
    full = family.global_attention_shape(config)
    assert full == {"batch": 2, "heads": 4, "kv_heads": 1, "length": 8192,
                    "qk_dim": 128, "v_dim": 128, "pairs": 33_558_528,
                    "layers": 1}
    assert family.global_attention_flops_per_step(full) == (
        1536 * 33_558_528 * 2 * 4)
    # other families' roofline readers must not apply to this one
    for name in ("attention_shape", "latent_attention_shape",
                 "window_attention_shape", "kda_scan_shape"):
        assert not hasattr(family, name), name


def test_data_is_tokens_of_the_slice_with_the_next_token_as_label():
    cell = real_cell()
    x, y = cell["family"].make_data(cell["config"],
                                    np.random.default_rng(2 ** 31 + 5), 3)
    assert x.shape == y.shape == (3, 8192) and x.dtype == np.int32
    assert 0 <= x.min() and x.max() < 16384
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
        assert "provisional" not in limit["from"], name
    for name in ("loss_gap", "first_grad_gap", "delta_gap",
                 "first_grad_error"):
        assert "seeds" in workload["limits"][name]["from"], name
    assert "control" in workload["limits"]["first_grad_error"]["from"]
    assert workload["limits"]["first_grad_error"]["limit"] < 0.5
    entry = Registry(ROOT).workloads[CELL]
    assert entry["config"] == CONFIG


def rehearse(name, seed):
    return run.run_cell(registry(), name, seed, 30.0, False,
                        rehearse_segments=3)


def test_the_whole_tiny_decoder_through_fit_meets_the_reference(capsys):
    """float32 program: `Trainer.fit` (loader, the model's own loss, FSA's
    dense tier, Adam) against `reference_steps` on the plain reference, to
    rounding, over three steps."""
    result = rehearse("tiny-nemotron-f32", 2 ** 31 + 77)
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
    cell = registry().cell("tiny-nemotron-f32")
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
        registry().cell("tiny-nemotron-f32"), 2 ** 31 + 77)


@pytest.mark.parametrize("fault, number, least, most", [
    ("gradient_scaled_by_half", "first_grad_gap", 0.5, 0.5),
    ("gradient_scaled_by_half", "first_grad_error", 0.5, 0.5),
    ("one_leaf_missing", "first_grad_gap", 1.0, 1.0),
    ("state_unchanged", "delta_gap", 1.0, 1.0),
    ("half_the_batch_left_out", "first_grad_error", 0.3, 2.0),
    ("half_the_batch_left_out", "loss_gap", 1e-3, 1.0),
])
def test_a_planted_fault_reads_what_the_limits_are_set_against(
        fault, number, least, most):
    """`benchmark/tools/planted_faults.py`, which gives the chip cell's
    limits their upper readings, on the tiny cell: each fault moves the
    number that is there to catch it, and every fault fails the cell's
    limits."""
    numbers = tiny_faults()[fault]
    assert least - 1e-6 <= numbers[number] <= most + 1e-6, numbers
    limits = registry().cell("tiny-nemotron-f32")["workload"]["limits"]
    assert check.verdict(numbers, limits)[0] is False


def empty_context(cell):
    """No trace, no table, no counters (the parent's program)."""
    return {"cell": cell, "trace": None,
            "loop_stats": {"steps": 4, "wall_s": 1.0, "phases": {}},
            "step_layers": None, "peaks": PEAKS}


@pytest.mark.parametrize("name", OWN_READERS + EXPERT_READERS + APPENDED_TO)
def test_readers_apply_where_listed_and_read_nothing_from_nothing(name):
    reg = Registry(ROOT)
    reader = {m.NAME: m for m in reg.layer_metrics()}[name]
    entry = [m for m in reg.spec["per_layer"] if m["name"] == name][0]
    listed = DECODERS[-1:] if name in OWN_READERS else DECODERS
    # a later cell may join a list; the cells of today are these
    assert set(listed) <= set(entry["workloads"])
    for cell in DECODERS + ["bertlarge-bsc-1c", "bertlarge-fsa-1c",
                            "resnet18-bsc-1c"]:
        assert reader.applies(reg.cell(cell)) == (cell in listed), cell
        assert (cell in entry["workloads"]) == (cell in listed), cell
    assert entry["moves"] == "samples_per_s_chip"
    assert entry["unit"] == reader.UNIT
    if name not in APPENDED_TO:
        assert entry["source"] == "device_trace"
        assert entry["layer"] == (
            "kernels" if name.startswith("ssd_scan") else "step program")
        assert entry["better"] == (
            "higher" if name.endswith("roofline_pct") else "lower")
    assert reader.read(empty_context(reg.cell(CELL))) is None


def test_the_new_entries_stand_behind_the_accepted_ones():
    """Appended: behind PR 36's `setup_peak_gib`, in the order the issue
    lists them (what a later PR appends stands behind these)."""
    names = [m["name"] for m in Registry(ROOT).spec["per_layer"]]
    at = names.index("setup_peak_gib")
    assert names[at + 1:at + 7] == OWN_READERS + EXPERT_READERS


@pytest.mark.parametrize("name", [
    "flash_attn_roofline_pct", "latent_attn_roofline_pct", "attention_ms",
    "kda_scan_ms", "kda_scan_roofline_pct", "window_attn_ms",
    "window_attn_roofline_pct", "global_attn_roofline_pct", "gqa_proj_ms"])
def test_other_families_readers_do_not_apply(name):
    """`global_attn_roofline_pct` and `gqa_proj_ms` among them, although
    this cell's attention layer opens their scopes: they key on another
    family's function (PERF.md, section 7)."""
    reg = Registry(ROOT)
    reader = {m.NAME: m for m in reg.layer_metrics()}[name]
    assert not reader.applies(reg.cell(CELL))


def test_scope_readers_join_the_trace_with_the_programs_table():
    from geomx_tpu.telemetry.layers import OpLayer
    reg = Registry(ROOT)
    readers = {m.NAME: m for m in reg.layer_metrics()}
    fb = "step/forward_backward/"
    table = {
        "fusion.1": OpLayer(fb + "ssd/scan", "kernels", "forward"),
        "fusion.2": OpLayer(fb + "ssd/scan", "kernels", "backward"),
        "fusion.3": OpLayer(fb + "ssd/proj", "step program", "forward"),
        "fusion.4": OpLayer(fb + "moe/latent", "step program", "backward"),
        "fusion.5": OpLayer(fb + "moe/shared", "step program", "forward"),
        "fusion.6": OpLayer(fb + "moe/route", "step program", "forward"),
        "fusion.7": OpLayer(fb + "moe/experts/moe/dispatch", "step program",
                            "forward")}
    ctx = {"cell": reg.cell(CELL), "step_layers": table, "peaks": PEAKS,
           "trace": {"steps": 2, "by_op_s": {
               "fusion.1": 0.02, "fusion.2": 0.04, "fusion.3": 0.08,
               "fusion.4": 0.01, "fusion.5": 0.3, "fusion.6": 0.006,
               "fusion.7": 0.002, "not.in.table": 9.0}}}
    assert readers["ssd_scan_ms"].read(ctx) == pytest.approx(30.0)
    assert readers["ssd_proj_ms"].read(ctx) == pytest.approx(40.0)
    assert readers["moe_latent_ms"].read(ctx) == pytest.approx(5.0)
    assert readers["moe_shared_ms"].read(ctx) == pytest.approx(150.0)
    assert readers["moe_route_ms"].read(ctx) == pytest.approx(3.0)
    assert readers["moe_experts_ms"].read(ctx) == pytest.approx(1.0)
    assert readers["moe_dispatch_ms"].read(ctx) == pytest.approx(1.0)
    # 980 MB / 819 GB/s = 1.197 ms (the bytes bind) of 30
    assert readers["ssd_scan_roofline_pct"].read(ctx) == pytest.approx(
        100 * 1.197 / 30.0, rel=1e-3)
    ctx["loop_stats"] = {"steps": 8, "counters": {
        "moe/dropped": {"count": 4, "total": 0.0, "last": 0.0, "max": 0.0},
        "moe/assignments_mean": {"count": 4, "total": 2816.0, "last": 704.0,
                                 "max": 704.0}}}
    assert readers["moe_dropped_pct"].read(ctx) == 0.0
    # 4 steps x 5 layers x 8 held x 704 arrived; 1% of them dropped
    ctx["loop_stats"]["counters"]["moe/dropped"]["total"] = 1126.4
    assert readers["moe_dropped_pct"].read(ctx) == pytest.approx(1.0)


def test_the_roofline_share_cannot_pass_100_at_the_floor_itself():
    """A scan that took exactly the least time reads 100; both bounds come
    from shapes, whatever implements the scan."""
    from geomx_tpu.telemetry.layers import OpLayer
    reg = Registry(ROOT)
    reader = {m.NAME: m for m in reg.layer_metrics()}["ssd_scan_roofline_pct"]
    cell = reg.cell(CELL)
    shape = cell["family"].ssd_scan_shape(cell["config"])
    least = max(cell["family"].ssd_scan_flops_per_step(shape) / 197e12,
                cell["family"].ssd_scan_bytes_per_step(shape) / 819e9)
    ctx = {"cell": cell, "peaks": PEAKS, "step_layers": {
        "k": OpLayer("step/forward_backward/ssd/scan", "kernels", "forward")},
        "trace": {"steps": 1, "by_op_s": {"k": least}}}
    assert reader.read(ctx) == pytest.approx(100.0)


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
    # whole tiles, and no less than what even routing sends the 8 held
    assert model.expert_pool % model.expert_rows == 0
    assert model.expert_pool == 2 * (16384 * 22 * 8 // 512) == 11264
    config["program"] = dict(program, loss_block_tokens=512,
                             expert_block_rows=128, expert_pool_places=4096,
                             remat_each_layer=False)
    other = cell["family"].build_model(config).cfg
    assert (other.loss_block, other.expert_rows, other.expert_pool,
            other.remat) == (512, 128, 4096, False)
    assert (model.ssd_chunk, model.mamba_heads, model.mamba_groups,
            model.num_heads, model.num_kv_heads, model.latent, model.top_k,
            model.num_experts, model.experts_held, model.shared_width) == (
        128, 16, 1, 4, 1, 1024, 22, 512, 8, 5376)
    assert model.expert_form == {"gated": False, "latent": 1024,
                                 "shared_width": 5376}
    # a row of the latent is whole tiles as a slab: the pools' rows go back
    # through the kernel (ops.dispatch.row_scatter_add)
    from geomx_tpu.ops import moe_rows_pallas
    assert moe_rows_pallas.slabs_are_whole(model.latent)


def test_the_accepted_decoders_build_the_trees_they_built():
    """Kimi's and Trinity's expert layers keep their SwiGLU kernels and
    their two-half blocks: the parameter names under a block are what
    their references read."""
    import jax
    for name, both in (("kimilinear-fsa-1c", 5), ("trinitymini-fsa-1c", 5)):
        cell = Registry(ROOT).cell(name)
        model = cell["family"].build_model(cell["config"])
        shapes = jax.eval_shape(lambda m=model: m.init(
            jax.random.PRNGKey(0), np.zeros((1, 64), np.int32)))["params"]
        layers = [k for k in shapes if k.startswith("layer")]
        assert len(layers) == both
        assert all(set(shapes[k]) == {"mixer", "ffn"} for k in layers)
        moe = shapes["layer3"]["ffn"]["core"]
        assert {"experts_gate_kernel", "experts_up_kernel",
                "experts_down_kernel", "shared_gate_kernel",
                "router_kernel"} <= set(moe)
        assert not any(k.startswith("latent_") for k in moe)
