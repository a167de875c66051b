"""`select_pack_ms` (benchmark/layer_metrics/select_pack_ms.py): the
reader on a hand-made context against the number worked out by hand,
None where it has nothing to read, and where it applies."""
import pytest

from bench_paths import ROOT

from benchmark.cells import Registry


@pytest.fixture(scope="module")
def reader():
    return {m.NAME: m for m in Registry(ROOT).layer_metrics()}[
        "select_pack_ms"]


def hand_made_ctx():
    from geomx_tpu.telemetry.layers import OpLayer, layer_of

    def op(scope):
        return OpLayer(scope, layer_of("/".join(scope.split("/")[-2:])), None)

    bucket = "step/sync_grads/dc_allreduce/bucket{}/"
    table = {
        # the select/pack of two buckets: the two passes' kernels and an
        # XLA op of the schedule between them (a bucket of several
        # tiles), the one kernel of a bucket of one tile, all under the
        # scope `compress` opens; the boundary probe is not
        "bsc_select_pack_count.3": op(bucket.format(0) + "bsc/select_pack"),
        "fusion.8": op(bucket.format(0) + "bsc/select_pack"),
        "bsc_select_pack_place.3": op(bucket.format(0) + "bsc/select_pack"),
        "bsc_select_pack.5": op(bucket.format(3) + "bsc/select_pack"),
        "sort.2": op(bucket.format(0) + "compress/boundary"),
        "bsc_scatter_add.7": op(bucket.format(0) + "compress/merge/"
                                "bsc/scatter_add"),
        "fusion.4": op("step/optimizer"),
    }
    by_op_s = {"bsc_select_pack_count.3": 0.009, "fusion.8": 0.0015,
               "bsc_select_pack_place.3": 0.030, "bsc_select_pack.5": 0.0006,
               "sort.2": 0.004, "bsc_scatter_add.7": 0.030,
               "fusion.4": 0.012, "not_in_table.11": 0.5}
    return {"trace": {"steps": 3, "by_op_s": by_op_s,
                      "busy_s_busiest": sum(by_op_s.values())},
            "step_layers": table}


def test_select_pack_ms_by_hand(reader):
    assert reader.read(hand_made_ctx()) == pytest.approx(1e3 * 0.0411 / 3)


@pytest.mark.parametrize("ctx", [
    {"trace": None, "step_layers": {}},
    {"trace": {"steps": 0, "by_op_s": {}}, "step_layers": {}},
    {"trace": hand_made_ctx()["trace"], "step_layers": None},
], ids=["no-trace", "no-steps", "no-table"])
def test_select_pack_ms_reads_none_where_there_is_nothing_to_read(reader, ctx):
    assert reader.read(ctx) is None


def test_select_pack_ms_applies_to_the_bsc_cells_only(reader):
    reg = Registry(ROOT)
    applies = {cell for cell in reg.workloads
               if reader.applies(reg.cell(cell))}
    assert applies == {"bertlarge-bsc-1c", "resnet18-bsc-1c"}
    declared = {m["name"]: m for m in reg.spec["per_layer"]}["select_pack_ms"]
    assert set(declared["workloads"]) == applies
    assert declared["unit"] == reader.UNIT and declared["layer"] == "kernels"
    assert declared["moves"] == "samples_per_s_chip"
