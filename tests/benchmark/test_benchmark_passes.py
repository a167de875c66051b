"""The readers of the step's passes and self time, of `kda/proj` and of
the drained device: each on a hand-made table or counter, on a program
that lacks what it reads (the parent of the PR that added them), and the
identity that ties the passes to `forward_ms` and `backward_ms`."""
import collections
import json

import pytest

from bench_paths import ROOT

from benchmark.cells import Registry
from benchmark.layer_metrics import _passes
from geomx_tpu.telemetry.layers import OpLayer

FB = "step/forward_backward"
NEW = ("recompute_ms", "fwd_bwd_self_ms", "kda_proj_ms", "drained_pct")


@pytest.fixture(scope="module")
def reg():
    return Registry(ROOT)


@pytest.fixture(scope="module")
def readers(reg):
    return {m.NAME: m for m in reg.layer_metrics()}


def table(recomputed=True):
    """A decoder's step in nine instructions; without `recomputed` the two
    recomputed ones are the backward's."""
    again = "recomputed" if recomputed else "backward"
    return {
        "fusion.1": OpLayer(FB, "step program", "forward", "first"),
        "fusion.2": OpLayer(f"{FB}/kda/proj", "step program", "forward",
                            "first"),
        "fusion.3": OpLayer(f"{FB}/kda/proj", "step program", "backward",
                            again),
        "fusion.4": OpLayer(f"{FB}/kda/proj", "step program", "backward",
                            "backward"),
        # the backward's doubled name stack is the bare scope still
        "copy.5": OpLayer(f"{FB}/{FB}", "step program", "backward", again),
        "fusion.6": OpLayer(f"{FB}/{FB}/block/norm", "step program",
                            "backward", "backward"),
        "fusion.7": OpLayer(f"{FB}/lm/embed", "step program", "forward",
                            "first"),
        "fusion.8": OpLayer("step/optimizer", "step program", None, None),
        "copy.9": OpLayer(None, None, None)}


def context(reg, ops, cell="kimilinear-fsa-1c"):
    seconds = {"fusion.1": 0.002, "fusion.2": 0.004, "fusion.3": 0.008,
               "fusion.4": 0.016, "copy.5": 0.032, "fusion.6": 0.064,
               "fusion.7": 0.128, "fusion.8": 0.256, "copy.9": 0.512,
               "not.in.table": 1.0}
    return {"cell": reg.cell(cell), "step_layers": ops,
            "trace": {"steps": 2, "by_op_s": seconds,
                      "busy_s_busiest": sum(seconds.values())}}


def test_the_new_readers_read_a_hand_made_table(reg, readers, capsys):
    ctx = context(reg, table())
    # copy.5 + fusion.3: 0.032 + 0.008 s over 2 steps
    assert readers["recompute_ms"].read(ctx) == pytest.approx(20.0)
    # fusion.1 + copy.5
    assert readers["fwd_bwd_self_ms"].read(ctx) == pytest.approx(17.0)
    # fusion.2 + fusion.3 + fusion.4, every pass
    assert readers["kda_proj_ms"].read(ctx) == pytest.approx(14.0)
    lines = [line for line in capsys.readouterr().out.splitlines()
             if line.startswith("PASSES ")]
    assert len(lines) == 1              # one line a run, whoever asks
    said = json.loads(lines[0][len("PASSES "):])
    assert said["ms_per_step"] == pytest.approx(
        {"first": 67.0, "recomputed": 20.0, "backward": 40.0, "self": 17.0})
    by = {(s, p): ms for s, p, ms in said["ms_per_step_by_scope_and_pass"]}
    assert by[("(self)", "recomputed")] == pytest.approx(16.0)
    assert by[("block/norm", "backward")] == pytest.approx(32.0)
    assert by[("lm/embed", "first")] == pytest.approx(64.0)
    assert ("step/optimizer", None) not in by


@pytest.mark.parametrize("recomputed", [True, False])
def test_the_passes_add_up_to_forward_and_backward(reg, readers, recomputed):
    ctx = context(reg, table(recomputed))
    passes = _passes.pass_seconds(ctx)
    forward = readers["forward_ms"].read(ctx)
    backward = readers["backward_ms"].read(ctx)
    assert 1e3 * passes["first"] / 2 == pytest.approx(forward)
    assert 1e3 * (passes["recomputed"] + passes["backward"]) / 2 \
        == pytest.approx(backward)
    want = 20.0 if recomputed else 0.0
    got = readers["recompute_ms"].read(ctx)
    assert got == pytest.approx(want) and isinstance(got, float)


def test_a_step_that_rematerialises_nothing_reads_a_true_zero(reg, readers):
    ctx = context(reg, table(recomputed=False), "bertlarge-fsa-1c")
    assert readers["recompute_ms"].read(ctx) == 0.0
    assert readers["fwd_bwd_self_ms"].read(ctx) == pytest.approx(17.0)


@pytest.mark.parametrize("name", NEW)
def test_nothing_is_read_from_nothing(reg, readers, name):
    """No trace, no table, no counters: None, and no raise."""
    ctx = {"cell": reg.cell("kimilinear-fsa-1c"), "trace": None,
           "loop_stats": None, "step_layers": None}
    assert readers[name].read(ctx) is None
    ctx = context(reg, None)
    ctx["loop_stats"] = {"steps": 4, "wall_s": 0.0, "phases": {}}
    assert readers[name].read(ctx) is None


def test_the_parents_program_gives_its_self_time_and_no_pass(
        reg, readers, monkeypatch):
    """A table of three-field entries and counters without `fit/drained`:
    the pass and the drained share are left out, the self time and
    `kda/proj` read what the parent's table holds."""
    from benchmark.layer_metrics import _step_layers
    Old = collections.namedtuple("OpLayer", "scope layer direction")
    monkeypatch.setattr(_step_layers, "program_layers", lambda: object())
    ctx = context(reg, {k: Old(*v[:3]) for k, v in table().items()})
    ctx["loop_stats"] = {"steps": 4, "wall_s": 2.0, "phases": {}}
    assert readers["recompute_ms"].read(ctx) is None
    assert readers["drained_pct"].read(ctx) is None
    assert readers["fwd_bwd_self_ms"].read(ctx) == pytest.approx(17.0)
    assert readers["kda_proj_ms"].read(ctx) == pytest.approx(14.0)


def test_drained_pct_is_the_counters_share_of_the_wall(reg, readers):
    ctx = {"cell": reg.cell("bertlarge-bsc-1c"), "trace": None,
           "loop_stats": {"steps": 100, "wall_s": 20.0, "phases": {},
                          "fit/drained": {"count": 99, "total_s": 0.6,
                                          "max_s": 0.01, "max_step": 7}}}
    assert readers["drained_pct"].read(ctx) == pytest.approx(3.0)


def test_the_new_entries_are_the_last_four_and_list_what_the_issue_says():
    with open(f"{ROOT}/BENCHMARK.json") as f:
        entries = json.load(f)["per_layer"]
    last = {e["name"]: e for e in entries[-4:]}
    assert tuple(last) == ("recompute_ms", "fwd_bwd_self_ms", "kda_proj_ms",
                           "drained_pct")
    assert last["kda_proj_ms"]["workloads"] == ["kimilinear-fsa-1c"]
    for name in ("recompute_ms", "fwd_bwd_self_ms", "drained_pct"):
        assert "workloads" not in last[name]
    assert last["drained_pct"]["source"] == "program_counter"
    assert last["drained_pct"]["layer"] == "entry / host loop"


def test_an_encoder_after_a_rehearsed_run_recomputes_nothing(readers):
    """What `run.py` does around the readers, at a tiny size: the table a
    fresh trainer makes of the encoder's step has a pass on every
    instruction under `step/forward_backward` and none recomputed, and the
    window's counters hold `fit/drained`."""
    from bench_paths import tiny_registry
    from benchmark import run
    from benchmark.layer_metrics import _step_layers
    tiny = tiny_registry()
    result = run.run_cell(tiny, "tiny-seqcls-bsc", seed=5, seconds=60.0,
                          trace=False, rehearse_segments=2)
    assert result["correct"]
    ctx = {"cell": tiny.cell("tiny-seqcls-bsc"), "trace": None}
    stats = _step_layers.loop_stats(ctx)
    assert stats["fit/drained"]["count"] == \
        stats["phases"]["fit/log_sync"]["count"] - 1
    assert 0.0 < readers["drained_pct"].read(ctx) < 100.0
    ops = _step_layers.step_table(ctx)
    ctx["trace"] = {"steps": 1, "by_op_s": dict.fromkeys(ops, 1.0),
                    "busy_s_busiest": float(len(ops))}
    assert readers["recompute_ms"].read(ctx) == 0.0
    passes = _passes.pass_seconds(ctx)
    assert passes["first"] + passes["backward"] == pytest.approx(
        (readers["forward_ms"].read(ctx) + readers["backward_ms"].read(ctx))
        / 1e3)
    # the encoder opens no scope of its own but attention's: its model is
    # the bare scope's self time
    assert readers["fwd_bwd_self_ms"].read(ctx) > 0.5 * 1e3 * (
        passes["first"] + passes["backward"])
