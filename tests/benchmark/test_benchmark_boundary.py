"""`boundary_ms` (benchmark/layer_metrics/boundary_ms.py): the reader on
hand-made contexts against the number worked out by hand: the parent's
table (three gathers and a sort a bucket under `compress/boundary`) and
the change's (the kernel `bsc_boundary_probe` where it streams, the
gathers where they stay, a sort either way), None where it has nothing
to read, where it applies, and the whole join on a tiny cell's own
table."""
import pytest

from bench_paths import ROOT

from benchmark.cells import Registry


@pytest.fixture(scope="module")
def reader():
    return {m.NAME: m for m in Registry(ROOT).layer_metrics()}["boundary_ms"]


def op(scope):
    from geomx_tpu.telemetry.layers import OpLayer, layer_of
    return OpLayer(scope, layer_of("/".join(scope.split("/")[-2:])), None)


BUCKET = "step/sync_grads/dc_allreduce/bucket{}/"
OUTSIDE = {
    # the select/pack and the decompress of the same bucket, the
    # optimizer: other scopes, other metrics
    "bsc_select_pack_count.3": op(BUCKET.format(0) + "bsc/select_pack"),
    "bsc_scatter_add.7": op(BUCKET.format(0)
                            + "compress/merge/bsc/scatter_add"),
    "fusion.4": op("step/optimizer"),
}
OUTSIDE_S = {"bsc_select_pack_count.3": 0.009, "bsc_scatter_add.7": 0.030,
             "fusion.4": 0.012, "not_in_table.11": 0.5}
# before PR 32: three gather fusions and a sort a bucket
PARENT = {**OUTSIDE,
          "fusion.627": op(BUCKET.format(0) + "compress/boundary"),
          "fusion.628": op(BUCKET.format(0) + "compress/boundary"),
          "fusion.629": op(BUCKET.format(0) + "compress/boundary"),
          "sort.2": op(BUCKET.format(0) + "compress/boundary"),
          "fusion.294": op(BUCKET.format(5) + "compress/boundary"),
          "sort.9": op(BUCKET.format(5) + "compress/boundary")}
PARENT_S = {**OUTSIDE_S, "fusion.627": 0.00036, "fusion.628": 0.00036,
            "fusion.629": 0.00032, "sort.2": 0.000021,
            "fusion.294": 0.00021, "sort.9": 0.000021}
# since: the kernel for a bucket it streams, the gathers for the one
# above the threshold, nothing but the sort for one no larger than the
# probe
CHANGE = {**OUTSIDE,
          "bsc_boundary_probe.4": op(BUCKET.format(1) + "compress/boundary"),
          "sort.3": op(BUCKET.format(1) + "compress/boundary"),
          "fusion.627": op(BUCKET.format(0) + "compress/boundary"),
          "fusion.628": op(BUCKET.format(0) + "compress/boundary"),
          "fusion.629": op(BUCKET.format(0) + "compress/boundary"),
          "sort.2": op(BUCKET.format(0) + "compress/boundary"),
          "fusion.88": op(BUCKET.format(7) + "compress/boundary"),
          "sort.9": op(BUCKET.format(7) + "compress/boundary")}
CHANGE_S = {**OUTSIDE_S, "bsc_boundary_probe.4": 0.00024, "sort.3": 0.000021,
            "fusion.627": 0.00036, "fusion.628": 0.00036,
            "fusion.629": 0.00032, "sort.2": 0.000021,
            "fusion.88": 0.000003, "sort.9": 0.000021}


def ctx(table, by_op_s, steps=3):
    return {"trace": {"steps": steps, "by_op_s": by_op_s,
                      "busy_s_busiest": sum(by_op_s.values())},
            "step_layers": table}


@pytest.mark.parametrize("table,seconds,want_ms", [
    (PARENT, PARENT_S, 1e3 * 0.001292 / 3),
    (CHANGE, CHANGE_S, 1e3 * 0.001346 / 3),
], ids=["gathers-and-sort", "kernel-gathers-and-dense"])
def test_boundary_ms_by_hand(reader, table, seconds, want_ms):
    assert reader.read(ctx(table, seconds)) == pytest.approx(want_ms)


def test_boundary_ms_is_in_no_kernel_name_metric():
    """`compress_kernels_ms` and `select_pack_roofline_pct` find kernels
    by name (`trace_reduce.family_time_s`); the probe's name is in
    neither family, so both read what they read before it existed."""
    from benchmark.layer_metrics import compress_kernels_ms
    from benchmark.trace_reduce import family_time_s
    def families(by_op_s):
        # what trace_reduce keeps beside `by_op_s`: the name less its
        # instruction number
        out = {}
        for name, s in by_op_s.items():
            family = name.rsplit(".", 1)[0]
            out[family] = out.get(family, 0.0) + s
        return {"steps": 3, "by_family_s": out}

    trace = families(CHANGE_S)
    without = families({name: s for name, s in CHANGE_S.items()
                        if not name.startswith("bsc_boundary_probe")})
    assert family_time_s(trace, ("bsc_boundary_probe",)) == 0.00024
    assert (family_time_s(trace, ("bsc_select_pack",))
            == family_time_s(without, ("bsc_select_pack",)) == 0.009)
    assert (compress_kernels_ms.read({"trace": trace})
            == compress_kernels_ms.read({"trace": without})
            == pytest.approx(1e3 * 0.039 / 3))


@pytest.mark.parametrize("context", [
    {"trace": None, "step_layers": {}},
    {"trace": {"steps": 0, "by_op_s": {}}, "step_layers": {}},
    {"trace": ctx(PARENT, PARENT_S)["trace"], "step_layers": None},
    ctx(OUTSIDE, OUTSIDE_S),
], ids=["no-trace", "no-steps", "no-table", "no-such-scope"])
def test_boundary_ms_reads_none_where_there_is_nothing_to_read(reader,
                                                               context):
    assert reader.read(context) is None


def test_boundary_ms_applies_to_the_bsc_cells_only(reader):
    reg = Registry(ROOT)
    applies = {cell for cell in reg.workloads
               if reader.applies(reg.cell(cell))}
    assert applies == {"bertlarge-bsc-1c", "resnet18-bsc-1c"}
    declared = {m["name"]: m for m in reg.spec["per_layer"]}["boundary_ms"]
    assert set(declared["workloads"]) == applies
    assert declared["unit"] == reader.UNIT
    assert declared["layer"] == "compression engine"
    assert declared["moves"] == "samples_per_s_chip"
    assert declared["source"] == "device_trace"


def test_boundary_ms_on_a_tiny_cells_own_table(reader):
    """The whole join at a tiny size: after a rehearsed run the table of
    the program's own step holds `compress/boundary` once a sparse
    bucket, a sort in each; with every instruction "running" for a
    millisecond the reader charges exactly those."""
    from bench_paths import TINY

    from benchmark import run
    from benchmark.layer_metrics import _step_layers
    reg = Registry(ROOT, extra=[TINY])
    result = run.run_cell(reg, "tiny-seqcls-bsc", 2 ** 31 + 32, 30.0, False,
                          rehearse_segments=1)
    assert result["correct"]
    context = {"cell": reg.cell("tiny-seqcls-bsc"), "trace": None}
    table = _step_layers.step_table(context)
    under = [name for name, entry in table.items()
             if entry.scope and "compress/boundary" in entry.scope]
    assert any(name.startswith("sort") for name in under), under
    assert all("sync_grads" in table[name].scope for name in under)
    assert reader.read(context) is None         # no trace yet
    context["trace"] = {"steps": 2, "by_op_s": dict.fromkeys(table, 1e-3),
                        "busy_s_busiest": 1e-3 * len(table)}
    assert reader.read(context) == pytest.approx(len(under) / 2)
