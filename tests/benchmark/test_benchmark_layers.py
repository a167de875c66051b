"""The per-layer metrics that read the program's own names
(`benchmark/layer_metrics/_step_layers.py`): each reader on a hand-made
context against numbers worked by hand, None where it has nothing to
read, and the whole join on a tiny cell after a rehearsed run."""
import pytest

from bench_paths import ROOT, tiny_registry

from benchmark.cells import Registry

NEW = ("forward_ms", "backward_ms", "optimizer_ms", "compress_engine_ms",
       "unscoped_device_pct", "input_wait_pct", "dispatch_ms")


@pytest.fixture(scope="module")
def readers():
    return {m.NAME: m for m in Registry(ROOT).layer_metrics()}


def op(scope, direction=None):
    from geomx_tpu.telemetry.layers import OpLayer, layer_of
    return OpLayer(scope, layer_of("/".join(scope.split("/")[-2:])),
                   direction)


def hand_made_ctx():
    from geomx_tpu.telemetry.layers import UNNAMED, UNSCOPED
    table = {
        "fusion.1": op("step/forward_backward", "forward"),
        "flash_attention_with_lse.2": op("step/forward_backward", "forward"),
        "fusion.3": op("step/forward_backward", "backward"),
        "fusion.4": op("step/optimizer"),
        "bsc_select_pack.5": op("step/sync_grads/dc_allreduce/bucket0/"
                                "bsc/select_pack"),
        "sort.6": op("step/sync_grads/dc_allreduce/bucket0/"
                     "compress/boundary"),
        "fusion.7": op("step/metrics"),
        "copy.8": UNNAMED,
        "add.9": UNSCOPED,
        "never_ran.10": op("step/optimizer"),
    }
    by_op_s = {"fusion.1": 0.010, "flash_attention_with_lse.2": 0.030,
               "fusion.3": 0.080, "fusion.4": 0.012,
               "bsc_select_pack.5": 0.100, "sort.6": 0.004,
               "fusion.7": 0.001, "copy.8": 0.002, "add.9": 0.0005,
               "not_in_table.11": 0.0005}
    loop = {"steps": 50, "wall_s": 10.0, "phases": {
        "fit/next_batch": {"count": 51, "total_s": 0.04, "max_s": 0.03,
                           "max_step": 48},
        "fit/dispatch": {"count": 50, "total_s": 0.25, "max_s": 0.02,
                         "max_step": 0}}}
    return {"trace": {"steps": 2, "by_op_s": by_op_s,
                      "busy_s_busiest": sum(by_op_s.values())},
            "step_layers": table, "loop_stats": loop}


def test_new_metrics_by_hand(readers, capsys):
    ctx = hand_made_ctx()
    got = {name: readers[name].read(ctx) for name in NEW}
    assert got["forward_ms"] == pytest.approx(1e3 * 0.040 / 2)
    assert got["backward_ms"] == pytest.approx(1e3 * 0.080 / 2)
    assert got["optimizer_ms"] == pytest.approx(1e3 * 0.012 / 2)
    assert got["compress_engine_ms"] == pytest.approx(1e3 * 0.104 / 2)
    assert got["unscoped_device_pct"] == pytest.approx(
        100.0 * 0.003 / 0.240)
    assert got["input_wait_pct"] == pytest.approx(0.4)
    assert got["dispatch_ms"] == pytest.approx(5.0)
    seconds = ctx["step_layer_seconds"]
    assert seconds["other_scoped"] == pytest.approx(0.001)
    assert seconds["unknown"] == pytest.approx(0.0005)
    assert seconds["total"] == pytest.approx(sum(
        ctx["trace"]["by_op_s"].values()))
    # one LAYERS line a process, however many metrics read the join
    assert capsys.readouterr().out.count("LAYERS ") == 1


def test_new_metrics_read_none_where_there_is_nothing_to_read(readers):
    no_trace = {"trace": None, "step_layers": {}, "loop_stats": None}
    no_table = {"trace": hand_made_ctx()["trace"], "step_layers": None,
                "loop_stats": None}
    for ctx in (no_trace, no_table):
        for name in NEW:
            assert readers[name].read(dict(ctx)) is None, name


def test_engine_metric_applies_to_bsc_cells_only(readers):
    reg = Registry(ROOT)
    applies = {cell: {n for n in NEW if readers[n].applies(reg.cell(cell))}
               for cell in reg.workloads}
    assert applies["bertlarge-fsa-1c"] == set(NEW) - {"compress_engine_ms"}
    assert applies["bertlarge-bsc-1c"] == set(NEW)
    assert applies["resnet18-bsc-1c"] == set(NEW)
    declared = {m["name"]: m for m in reg.spec["per_layer"]}
    for name in NEW:
        cells = declared[name].get("workloads", list(reg.workloads))
        assert set(cells) == {c for c in applies if name in applies[c]}


def test_table_and_loop_stats_after_a_rehearsed_run(readers):
    """What `run.py` does around the readers, at a tiny size: the window's
    `fit` is left by the benchmark's `log_fn`, the trainer is dropped, and
    a fresh trainer makes the table from the recorded signature."""
    from benchmark import run
    from benchmark.layer_metrics import _step_layers
    reg = tiny_registry()
    result = run.run_cell(reg, "tiny-seqcls-bsc", seed=3, seconds=60.0,
                          trace=False, rehearse_segments=2)
    assert result["correct"]
    ctx = {"cell": reg.cell("tiny-seqcls-bsc"), "trace": None}
    stats = _step_layers.loop_stats(ctx)
    log_every = ctx["cell"]["workload"]["log_every"]
    assert stats["steps"] == 2 * log_every
    assert stats["phases"]["fit/log_fn"]["count"] == 2
    assert readers["dispatch_ms"].read(ctx) > 0
    assert 0 <= readers["input_wait_pct"].read(ctx) < 100
    table = _step_layers.step_table(ctx)
    scopes = {v.scope for v in table.values() if v.scope}
    assert "step/optimizer" in scopes
    assert any(s.startswith("step/sync_grads/dc_allreduce/bucket")
               for s in scopes)
    directions = {v.direction for v in table.values()}
    assert {"forward", "backward"} <= directions
    # every instruction "ran" for a second: the join charges all of them
    ctx["trace"] = {"steps": 1, "by_op_s": dict.fromkeys(table, 1.0),
                    "busy_s_busiest": float(len(table))}
    seconds = _step_layers.layer_seconds(ctx)
    assert seconds["unknown"] == 0
    assert seconds["total"] == len(table)
    assert seconds["forward"] > 0 and seconds["backward"] > 0
    assert seconds["sync_grads"] > 0 and seconds["optimizer"] > 0


def test_trace_spans_tool_names_the_recorded_gap():
    """`benchmark/tools/trace_spans.py` on the trace recorded on the chip:
    the 30 ms idle gap sits under the recorded `bench/host_sleep` span."""
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "trace_spans", os.path.join(ROOT, "benchmark", "tools",
                                    "trace_spans.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    ops, modules, host = tool.read_host_and_device(
        os.path.join(ROOT, "benchmark", "testdata", "small.xplane.pb"))
    assert len(modules) == 5
    lo, hi = min(a for a, _ in modules), max(b for _, b in modules)
    gaps = sorted(tool.gaps_over(ops, lo, hi, 1e6),
                  key=lambda g: g[0] - g[1])
    assert (gaps[0][1] - gaps[0][0]) / 1e6 == pytest.approx(31.78, abs=0.01)
    chain = tool.enclosing_chain(gaps[0], host)
    assert [c["span"] for c in chain] == ["bench/host_sleep"]
    rows = tool.host_summary(host, lo, hi, len(modules))
    assert rows[0]["name"] == "bench/host_sleep" and rows[0]["count"] == 1
    assert rows[0]["total_ms"] == pytest.approx(30.585, abs=0.001)
