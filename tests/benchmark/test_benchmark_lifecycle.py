"""The eight readers of the program's lifecycle record
(benchmark/layer_metrics/_lifecycle.py and `init_state_s`,
`first_dispatch_s`, `step_trace_lower_s`, `step_fetch_or_compile_s`,
`setup_programs`, `state_gib`, `step_reserved_gib`, `setup_peak_gib`): by
hand on a made record, None where the program has none, and after a
rehearsed tiny run, where the fresh trainer that makes the step's table
afterwards changes none of them."""
import json

import pytest

from bench_paths import ROOT, tiny_registry

from benchmark.cells import Registry

NAMES = ("init_state_s", "first_dispatch_s", "step_trace_lower_s",
         "step_fetch_or_compile_s", "setup_programs", "state_gib",
         "step_reserved_gib", "setup_peak_gib")
GIB = 2 ** 30


@pytest.fixture(scope="module")
def readers():
    return {m.NAME: m for m in Registry(ROOT).layer_metrics()}


def occurrence(t, fun_name, phase, trace_s=0.0, lower_s=0.0, backend_s=0.0,
               cache="hit"):
    return {"t": t, "fun_name": fun_name, "phase": phase, "step": 0,
            "trace_s": trace_s, "lower_s": lower_s, "backend_s": backend_s,
            "cache": cache, "retrieval_s": 0.0}


def reading(t, in_use, reserved, peak):
    now = {"t": t, "step": 0, "bytes_in_use": in_use,
           "bytes_reserved": reserved, "peak_bytes_in_use": peak}
    return {"count": 1, "first": now, "last": now,
            "max": {k: now[k] for k in ("bytes_in_use", "bytes_reserved",
                                        "peak_bytes_in_use")}}


def made_record():
    """A warm BERT-sized start as `Lifecycle.as_dict()` gives it: 14 s of
    init_state, a first dispatch of 11 s of which 6.5 trace, 1.5 lower
    and 2.5 the cache's read and load; 5 GiB of state, 3 GiB reserved by
    the loaded step; set-up's high-water mark 5.5 GiB."""
    step = occurrence(131.0, "_device_step", "fit/first_dispatch",
                      trace_s=6.5, lower_s=1.5, backend_s=2.5)
    return {
        "step_fun": "_device_step",
        "spans": {
            "setup/build": {"begin": 100.0, "seconds": 0.5, "total_s": 0.5,
                            "count": 1},
            "setup/init_state": {"begin": 101.0, "seconds": 14.0,
                                 "total_s": 14.0, "count": 1},
            "fit/first_dispatch": {"begin": 120.5, "seconds": 11.0,
                                   "total_s": 11.0, "count": 1}},
        "marks": {
            "fit/first_dispatch": reading(120.5, 5 * GIB, GIB // 4,
                                          5 * GIB + GIB // 2),
            "fit/first_boundary": reading(140.0, 5 * GIB, 3 * GIB + GIB // 4,
                                          9 * GIB),
            # a later fit's end does not move the first readings
            "fit/end": reading(300.0, 5 * GIB, 4 * GIB, 10 * GIB)},
        "state_bytes": {"params": 1.25 * GIB, "opt_state": 2.5 * GIB,
                        "sync_state": 1.25 * GIB, "model_state": 0.0},
        "step_reserved_bytes": 3 * GIB,
        "first_boundary_t": 140.0,
        "step_program": step,
        "programs": {"totals": {}, "dropped": 0, "by_fun": {}, "occurrences": [
            occurrence(99.0, "convert_element_type", "outside"),
            occurrence(103.0, "<lambda>", "setup/model_init"),
            occurrence(110.0, "broadcast_in_dim", "setup/state_init"),
            step,
            occurrence(139.0, "gradient", "outside"),
            # the table's second lowering of the step, after the boundary
            occurrence(400.0, "_device_step", "outside", trace_s=6.0,
                       lower_s=1.5, backend_s=2.0)]},
    }


BY_HAND = {"init_state_s": 14.0, "first_dispatch_s": 11.0,
           "step_trace_lower_s": 8.0, "step_fetch_or_compile_s": 2.5,
           "setup_programs": 5, "state_gib": 5.0, "step_reserved_gib": 3.0,
           "setup_peak_gib": 5.5}


@pytest.mark.parametrize("name", NAMES)
def test_reader_by_hand_on_a_made_record(readers, name):
    assert readers[name].read({"lifecycle": made_record()}) == BY_HAND[name]


@pytest.mark.parametrize("name", NAMES)
def test_reader_gives_none_where_the_program_has_no_record(
        readers, name, monkeypatch):
    from benchmark.layer_metrics import _step_layers
    assert readers[name].read({"lifecycle": None}) is None

    class Parent:
        """`telemetry.layers` as the parent has it: no `last_lifecycle`."""

    monkeypatch.setattr(_step_layers, "program_layers", lambda: Parent)
    ctx = {}
    assert readers[name].read(ctx) is None and ctx["lifecycle"] is None
    monkeypatch.setattr(_step_layers, "program_layers", lambda: None)
    assert readers[name].read({}) is None


def test_readers_give_none_for_what_a_record_lacks(readers):
    """Before the first boundary, without a step's occurrence, on a
    backend without allocator statistics (the CPU): each reader alone."""
    record = made_record()
    record.update(first_boundary_t=None, step_program=None, state_bytes={})
    del record["marks"]["fit/first_boundary"]
    for field in ("bytes_in_use", "bytes_reserved", "peak_bytes_in_use"):
        record["marks"]["fit/first_dispatch"]["first"][field] = None
    ctx = {"lifecycle": record}
    for name in ("step_trace_lower_s", "step_fetch_or_compile_s",
                 "setup_programs", "state_gib", "step_reserved_gib",
                 "setup_peak_gib"):
        assert readers[name].read(ctx) is None, name
    assert readers["init_state_s"].read(ctx) == 14.0
    del record["spans"]["setup/init_state"]
    assert readers["init_state_s"].read(ctx) is None


def test_every_new_metric_is_declared_for_every_cell(readers):
    spec = json.load(open(ROOT + "/BENCHMARK.json"))
    declared = {m["name"]: m for m in spec["per_layer"]}
    e2e = {m["name"] for m in spec["end_to_end"]}
    for name in NAMES:
        entry = declared[name]
        assert "workloads" not in entry and entry["better"] == "lower"
        assert entry["source"] == "program_counter"
        assert entry["moves"] in ("setup_s", "peak_hbm_gib")
        assert entry["moves"] in e2e
        assert entry["unit"] == readers[name].UNIT
    # appended behind everything PR 34 had; a later PR appends behind them
    names = [m["name"] for m in spec["per_layer"]]
    first = names.index("moe_dispatch_ms") + 1
    assert names[first:first + len(NAMES)] == list(NAMES)


def test_after_a_rehearsed_run_and_a_fresh_trainer(readers, capsys):
    """What `run.py` does around the readers, at a tiny size on the CPU:
    the times and the count are there, the allocator's numbers are not,
    and `step_table`'s fresh trainer with its second lowering moves
    nothing."""
    from benchmark import run
    from benchmark.layer_metrics import _lifecycle, _step_layers
    from geomx_tpu.telemetry import layers
    layers.compile_log().clear()
    reg = tiny_registry()
    result = run.run_cell(reg, "tiny-seqcls-bsc", seed=5, seconds=60.0,
                          trace=False, rehearse_segments=2)
    assert result["correct"]
    capsys.readouterr()
    ctx = {"cell": reg.cell("tiny-seqcls-bsc"), "trace": None}
    got = {name: readers[name].read(ctx) for name in NAMES}
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("LIFECYCLE ")]
    assert len(lines) == 1          # read and printed once
    assert json.loads(lines[0][len("LIFECYCLE "):]) == ctx["lifecycle"]
    for name in ("init_state_s", "first_dispatch_s", "step_trace_lower_s",
                 "step_fetch_or_compile_s", "setup_programs", "state_gib"):
        assert got[name] > 0, name
    assert got["step_trace_lower_s"] + got["step_fetch_or_compile_s"] \
        <= got["first_dispatch_s"]
    assert got["step_reserved_gib"] is None         # no statistics here
    assert got["setup_peak_gib"] is None
    record = ctx["lifecycle"]
    # three fits of one step, then the window's: one first dispatch
    assert record["spans"]["fit/first_dispatch"]["count"] == 1
    assert record["marks"]["fit/end"]["count"] == \
        ctx["cell"]["traffic"]["n_check"] + 1
    assert record["step_program"]["phase"] == "fit/first_dispatch"
    # Bi-Sparse keeps u and v a party beside Adam's m and v
    sizes = record["state_bytes"]
    assert sizes["sync_state"] > 0 and sizes["opt_state"] > sizes["params"]

    before = layers.compile_log().compiles
    assert _step_layers.step_table(ctx) is not None
    assert layers.compile_log().compiles > before   # the second lowering
    fresh = {"cell": ctx["cell"], "trace": None}
    again = {name: readers[name].read(fresh) for name in NAMES}
    assert again == got
    assert len(_lifecycle.setup_occurrences(fresh)) == got["setup_programs"]
    assert len(fresh["lifecycle"]["programs"]["occurrences"]) > \
        got["setup_programs"]
