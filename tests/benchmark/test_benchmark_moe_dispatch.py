"""`moe_dispatch_ms` (benchmark/layer_metrics/moe_dispatch_ms.py): the
reader on hand-made contexts against the number worked out by hand: the
parent's table (no scope `moe/dispatch`: XLA's gathers and scatter-adds
stand under `moe/experts` alone), the change's (XLA's gathers, the
scatter-add kernel and the fill and layout pass at its doors under
`moe/experts/moe/dispatch`), None
where it has nothing to read, and where it applies."""
import pytest

from bench_paths import ROOT

from benchmark.cells import Registry

DECODERS = {"kimilinear-fsa-1c", "trinitymini-fsa-1c"}


@pytest.fixture(scope="module")
def readers():
    return {m.NAME: m for m in Registry(ROOT).layer_metrics()}


def op(scope, direction="forward"):
    from geomx_tpu.telemetry.layers import OpLayer, layer_of
    return OpLayer(scope, layer_of("/".join(scope.split("/")[-2:])),
                   direction)


EXPERTS = "step/forward_backward/moe/experts"
OUTSIDE = {
    "sort.3": op(EXPERTS), "gmm.5": op(EXPERTS),
    "tgmm.9": op(EXPERTS, "backward"),
    "fusion.4": op("step/forward_backward/moe/route"),
    "fusion.8": op("step/forward_backward/lm/loss")}
OUTSIDE_S = {"sort.3": 0.003, "gmm.5": 0.012, "tgmm.9": 0.015,
             "fusion.4": 0.004, "fusion.8": 0.040, "not_in_table.1": 0.5}
# before PR 34: the moves are XLA's, under `moe/experts` like the rest
PARENT = {**OUTSIDE, "fusion.21": op(EXPERTS),
          "fusion.22": op(EXPERTS, "backward")}
PARENT_S = {**OUTSIDE_S, "fusion.21": 0.0073, "fusion.22": 0.0132}
# since: the gathers, the kernel, the slabs' fill and layout pass, under
# the nested scope
MOVES = EXPERTS + "/moe/dispatch"
CHANGE = {**OUTSIDE,
          "fusion.2": op(MOVES),
          "moe_row_scatter_add.4": op(MOVES),
          "broadcast.31": op(MOVES),
          "copy_bitcast_fusion.3": op(MOVES),
          "fusion.6": op(MOVES, "backward"),
          "fusion.7": op(MOVES, "backward"),
          "moe_row_scatter_add.8": op(MOVES, "backward"),
          "copy_bitcast_fusion.9": op(MOVES, "backward")}
CHANGE_S = {**OUTSIDE_S, "fusion.2": 0.0002,
            "moe_row_scatter_add.4": 0.0027, "broadcast.31": 0.0005,
            "copy_bitcast_fusion.3": 0.0009,
            "fusion.6": 0.0002, "fusion.7": 0.0002,
            "moe_row_scatter_add.8": 0.0018, "copy_bitcast_fusion.9": 0.0010}


def ctx(table, by_op_s, steps=3):
    return {"trace": {"steps": steps, "by_op_s": by_op_s},
            "step_layers": table}


def test_the_scope_is_one_of_the_vocabulary_and_nests_in_the_experts():
    from geomx_tpu.telemetry.layers import classify_op_name, layer_of
    assert layer_of("moe/dispatch") == "step program"
    got = classify_op_name(
        "jit(_device_step)/step/forward_backward/transpose(jvp(Afmoe))/"
        "checkpoint/layer3/ffn/core/moe/experts/moe/dispatch/pallas_call")
    assert got.scope == MOVES
    assert got.layer == "step program" and got.direction == "backward"


def test_moe_dispatch_ms_by_hand(readers):
    reader = readers["moe_dispatch_ms"]
    assert reader.read(ctx(CHANGE, CHANGE_S)) == pytest.approx(
        1e3 * 0.0075 / 3)
    # a part of the experts' time, which still holds all of it
    assert readers["moe_experts_ms"].read(ctx(CHANGE, CHANGE_S)) == \
        pytest.approx(1e3 * (0.030 + 0.0075) / 3)


@pytest.mark.parametrize("context", [
    {"trace": None, "step_layers": {}},
    {"trace": {"steps": 0, "by_op_s": {}}, "step_layers": {}},
    {"trace": ctx(CHANGE, CHANGE_S)["trace"], "step_layers": None},
    ctx(PARENT, PARENT_S),
], ids=["no-trace", "no-steps", "no-table", "the-parent-has-no-such-scope"])
def test_moe_dispatch_ms_reads_none_where_there_is_nothing_to_read(
        readers, context):
    assert readers["moe_dispatch_ms"].read(context) is None


@pytest.mark.parametrize("cell", sorted(Registry(ROOT).workloads))
def test_moe_dispatch_ms_applies_to_the_two_decoder_cells_only(readers,
                                                               cell):
    reg = Registry(ROOT)
    reader = readers["moe_dispatch_ms"]
    assert reader.applies(reg.cell(cell)) == (cell in DECODERS)
    declared = {m["name"]: m for m in reg.spec["per_layer"]}[reader.NAME]
    assert set(declared["workloads"]) == DECODERS
    assert (declared["unit"], declared["better"]) == (reader.UNIT, "lower")
    assert declared["layer"] == "step program"
    assert declared["moves"] == "samples_per_s_chip"
    assert declared["source"] == "device_trace"
    assert reg.spec["per_layer"][-1] is declared      # appended, at the end
