"""BENCHMARK.json against the contract's limits, and the harness's
look-up by name: every cell's files resolve, and a configuration, a
traffic mix, a family, a per-layer metric and a cell can each be added as
new files and new entries without editing a file that is there."""
import json
import os
import re
import shutil

import pytest

from bench_paths import ROOT

from benchmark.cells import Registry

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    SPEC = json.load(_f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTHS = re.compile(r"(hidden|intermediate|latent|state|proj).*size|_dim$|"
                    r"_rank$|head_dim|expansion|experts_per_tok")


def all_names():
    out = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        out += [(group, e["name"]) for e in SPEC[group]]
    out += [("config", w["config"]) for w in SPEC["workloads"]]
    out += [("traffic", w["traffic"]) for w in SPEC["workloads"]]
    out += [("reduced", k) for c in SPEC["configs"] for k in c["reduced"]]
    return out


def test_top_level_keys_and_limits():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(SPEC["paths"]) <= 16 and len(SPEC["command"]) <= 32
    assert isinstance(SPEC["run_seconds"], int)
    assert 1 <= SPEC["run_seconds"] <= 51
    cells = len(SPEC["workloads"])
    assert 1 <= cells <= 24
    # a full check with all 24 cells has to fit
    assert (2 + 14 * 24) * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, cells // 4)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


@pytest.mark.parametrize("group,name", all_names())
def test_names_use_allowed_characters(group, name):
    assert NAME.match(name), (group, name)


@pytest.mark.parametrize("metric", SPEC["end_to_end"] + SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_entries(metric):
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    cells = {w["name"] for w in SPEC["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
    if "bound" in metric:       # end to end
        assert set(metric) <= {"name", "unit", "better", "bound", "source",
                               "workloads"}
        assert 0.01 <= metric["bound"] <= 0.1
        assert metric["source"] in ("host_clock", "device_trace")
    else:
        assert set(metric) <= {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
        assert metric["moves"] in {m["name"] for m in SPEC["end_to_end"]}
        assert 1 <= len(metric["layer"]) <= 200 and "\n" not in metric["layer"]


def test_metric_and_cell_names_are_unique_and_setup_is_there():
    metrics = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(metrics) == len(set(metrics))
    cells = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(cells) == len(set(cells))
    assert "setup_s" in {m["name"] for m in SPEC["end_to_end"]}
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}


@pytest.mark.parametrize("config", SPEC["configs"], ids=lambda c: c["name"])
def test_config_entry_and_file(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert config["file"].startswith(tuple(p + "/" for p in SPEC["paths"]))
    with open(os.path.join(ROOT, config["file"])) as f:
        data = json.load(f)
    assert data["source"] == config["source"]
    assert data["reduced"] == config["reduced"]
    assert not any(WIDTHS.search(k) for k in config["reduced"])
    assert 1 <= len(config["why"]) <= 200 and len(config["source"]) <= 200


@pytest.mark.parametrize("workload", SPEC["workloads"], ids=lambda w: w["name"])
def test_every_workloads_files_resolve_by_name(workload):
    assert set(workload) == {"name", "config", "traffic", "chips", "why"}
    assert workload["chips"] in (1, 4) and 1 <= len(workload["why"]) <= 200
    cell = Registry(ROOT).cell(workload["name"])
    assert cell["traffic"]["parties"] * cell["traffic"]["workers"] == cell["chips"]
    assert cell["workload"]["log_every"] >= 1
    for number in ("loss_gap", "first_grad_gap", "first_grad_error", "delta_gap",
                   "nonfinite_losses", "compiles_in_window"):
        assert "limit" in cell["workload"]["limits"][number]
    for fn in ("build_model", "make_data", "weight_std", "reference_loss",
               "train_flops_per_sample"):
        assert callable(getattr(cell["family"], fn))


@pytest.mark.parametrize("metric", SPEC["per_layer"], ids=lambda m: m["name"])
def test_every_per_layer_metric_has_a_reader_that_applies_where_listed(metric):
    reg = Registry(ROOT)
    readers = {m.NAME: m for m in reg.layer_metrics()}
    reader = readers[metric["name"]]
    assert reader.UNIT == metric["unit"]
    applies = {w["name"] for w in SPEC["workloads"]
               if reader.applies(reg.cell(w["name"]))}
    assert applies == set(metric.get(
        "workloads", [w["name"] for w in SPEC["workloads"]]))


def test_files_under_paths_are_named_from_a_names_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in SPEC["paths"]:
        for folder, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                rel = os.path.relpath(os.path.join(folder, name), ROOT)
                assert ok.match(rel), rel


def test_a_cell_added_as_new_files_only_is_found(tmp_path):
    root = tmp_path / "repo"
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*")
              if p.is_file()}
    bench = root / "benchmark"
    (bench / "configs" / "new-model.json").write_text(json.dumps(
        {"family": "newfam", "source": "a paper", "reduced": [], "width": 8}))
    (bench / "traffic" / "new-mix.json").write_text(json.dumps(
        {"parties": 1, "workers": 1, "geoconfig": {"compression": "none"},
         "bucket_bytes": 1024, "n_check": 1}))
    (bench / "workloads" / "new-cell.json").write_text(json.dumps(
        {"log_every": 3, "limits": {}}))
    (bench / "families" / "newfam.py").write_text(
        "def train_flops_per_sample(config):\n    return 6.0 * config['width']\n")
    (bench / "layer_metrics" / "new_metric.py").write_text(
        "NAME, UNIT = 'new_metric', 'count'\n"
        "def applies(cell):\n    return cell['config']['family'] == 'newfam'\n"
        "def read(ctx):\n    return 7\n")
    spec = dict(SPEC)
    spec["workloads"] = SPEC["workloads"] + [
        {"name": "new-cell", "config": "new-model", "traffic": "new-mix",
         "chips": 1, "why": "shown by a test"}]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    reg = Registry(str(root))
    cell = reg.cell("new-cell")
    assert cell["workload"]["log_every"] == 3
    assert cell["family"].train_flops_per_sample(cell["config"]) == 48.0
    readers = {m.NAME: m for m in reg.layer_metrics()}
    assert readers["new_metric"].applies(cell)
    assert readers["new_metric"].read({}) == 7
    old = reg.cell(SPEC["workloads"][0]["name"])
    assert not readers["new_metric"].applies(old)
    assert readers["mfu_pct"].applies(cell)      # fits by the cell's data
    assert not readers["compress_kernels_ms"].applies(cell)
    # nothing that was there has been edited
    assert all(p.read_bytes() == data for p, data in before.items())


def test_an_unknown_cell_or_a_bad_name_is_an_error():
    reg = Registry(ROOT)
    with pytest.raises(KeyError):
        reg.cell("no-such-cell")
    with pytest.raises(ValueError):
        reg.find("configs", "../escape", ".json")
