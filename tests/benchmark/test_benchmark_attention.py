"""`attention_ms` (benchmark/layer_metrics/attention_ms.py): the reader on
hand-made contexts against the number worked out by hand: a table whose
`attn/core` scope nests in a decoder's `mla/attention`, a classifier's
where it stands alone, None where the program has no such scope (the
parent of the PR that added it), and where it applies."""
import pytest

from bench_paths import ROOT

from benchmark.cells import Registry


@pytest.fixture(scope="module")
def reader():
    return {m.NAME: m for m in Registry(ROOT).layer_metrics()}[
        "attention_ms"]


def op(scope, direction="forward"):
    from geomx_tpu.telemetry.layers import OpLayer, layer_of
    return OpLayer(scope, layer_of("/".join(scope.split("/")[-2:])),
                   direction)


FB = "step/forward_backward/"
# a decoder's step: the forward kernel twice (the mixer is rematerialised),
# the two backward kernels and delta's multiply-reduce under `attn/core`
# inside `mla/attention`; the key's concatenate under `mla/attention`
# alone, a projection and the KDA scan outside both
NESTED = {
    "flash_attention_fwd.3": op(FB + "mla/attention/attn/core"),
    "flash_attention_fwd.9": op(FB + "mla/attention/attn/core", "backward"),
    "flash_attention_bwd_dq.4": op(FB + "mla/attention/attn/core",
                                   "backward"),
    "flash_attention_bwd_dkv.5": op(FB + "mla/attention/attn/core",
                                    "backward"),
    "fusion.61": op(FB + "mla/attention/attn/core", "backward"),
    "fusion.62": op(FB + "mla/attention"),
    "fusion.7": op(FB + "mla/proj"),
    "fusion.8": op(FB + "kda/scan"),
}
NESTED_S = {"flash_attention_fwd.3": 0.027, "flash_attention_fwd.9": 0.027,
            "flash_attention_bwd_dq.4": 0.045,
            "flash_attention_bwd_dkv.5": 0.048, "fusion.61": 0.0015,
            "fusion.62": 0.006, "fusion.7": 0.072, "fusion.8": 1.7,
            "not_in_table.11": 0.5}
# a classifier's: the scope directly under the step's
ALONE = {"flash_attention_fwd.2": op(FB + "attn/core"),
         "flash_attention_bwd.2": op(FB + "attn/core", "backward"),
         "fusion.12": op(FB[:-1]),
         "fusion.4": op("step/optimizer")}
ALONE_S = {"flash_attention_fwd.2": 0.030, "flash_attention_bwd.2": 0.063,
           "fusion.12": 0.3, "fusion.4": 0.012}
# the parent's table: the same kernels under `mla/attention`, no `attn/core`
PARENT = {"flash_attention_with_lse.15": op(FB + "mla/attention"),
          "flash_attention_bwd.22": op(FB + "mla/attention", "backward"),
          "fusion.7": op(FB + "mla/proj")}
PARENT_S = {"flash_attention_with_lse.15": 0.368,
            "flash_attention_bwd.22": 0.430, "fusion.7": 0.072}


def ctx(table, by_op_s, steps=3):
    return {"trace": {"steps": steps, "by_op_s": by_op_s,
                      "busy_s_busiest": sum(by_op_s.values())},
            "step_layers": table}


@pytest.mark.parametrize("table,seconds,want_ms", [
    (NESTED, NESTED_S, 1e3 * 0.1485 / 3),
    (ALONE, ALONE_S, 1e3 * 0.093 / 3),
], ids=["nested-in-mla-attention", "directly-under-the-step"])
def test_attention_ms_by_hand(reader, table, seconds, want_ms):
    assert reader.read(ctx(table, seconds)) == pytest.approx(want_ms)


def test_attention_ms_leaves_what_mla_attention_read_as_it_was():
    """`latent_attn_roofline_pct` matches `mla/attention` by containment,
    so the nested scope's instructions still count there."""
    from benchmark.layer_metrics import _scopes
    got = _scopes.scope_ms(ctx(NESTED, NESTED_S), "mla/attention")
    assert got == pytest.approx(1e3 * 0.1545 / 3)


@pytest.mark.parametrize("context", [
    {"trace": None, "step_layers": {}},
    {"trace": {"steps": 0, "by_op_s": {}}, "step_layers": {}},
    {"trace": ctx(NESTED, NESTED_S)["trace"], "step_layers": None},
    ctx(PARENT, PARENT_S),
], ids=["no-trace", "no-steps", "no-table", "no-such-scope"])
def test_attention_ms_reads_none_where_there_is_nothing_to_read(reader,
                                                                context):
    assert reader.read(context) is None


def test_attention_ms_applies_to_the_attention_cells_only(reader):
    reg = Registry(ROOT)
    applies = {cell for cell in reg.workloads
               if reader.applies(reg.cell(cell))}
    assert applies == {"bertlarge-bsc-1c", "bertlarge-fsa-1c",
                       "kimilinear-fsa-1c"}
    declared = {m["name"]: m for m in reg.spec["per_layer"]}["attention_ms"]
    assert set(declared["workloads"]) == applies
    assert declared["unit"] == reader.UNIT and declared["layer"] == "kernels"
    assert declared["moves"] == "samples_per_s_chip"
    assert declared["source"] == "device_trace"


@pytest.mark.parametrize("cell,extra,nested_in", [
    ("tiny-seqcls-dense", "data", "step/forward_backward/attn/core"),
    ("tiny-kimi-f32", "data_kimi", "mla/attention/attn/core"),
], ids=["classifier", "decoder"])
def test_attention_ms_on_a_tiny_cells_own_table(reader, cell, extra,
                                                nested_in):
    """The whole join at a tiny size: after a rehearsed run the table of
    the program's own step holds `attn/core`, directly under the step's
    scope in a classifier and nested in `mla/attention` in a decoder,
    forward and backward; with every instruction "running" for a
    millisecond the reader charges exactly those."""
    import os

    from bench_paths import TINY

    from benchmark import run
    from benchmark.layer_metrics import _step_layers
    reg = Registry(ROOT, extra=[os.path.join(os.path.dirname(TINY), extra),
                                TINY])
    result = run.run_cell(reg, cell, 2 ** 31 + 30, 30.0, False,
                          rehearse_segments=1)
    assert result["correct"]
    context = {"cell": reg.cell(cell), "trace": None}
    table = _step_layers.step_table(context)
    under = [name for name, entry in table.items()
             if entry.scope and "attn/core" in entry.scope]
    # (a loop's body hands its scope down without the step's own prefix)
    assert all(table[name].scope.endswith(nested_in) for name in under)
    assert {"forward", "backward"} <= {table[name].direction
                                       for name in under}
    assert reader.read(context) is None         # no trace yet
    context["trace"] = {"steps": 2, "by_op_s": dict.fromkeys(table, 1e-3),
                        "busy_s_busiest": 1e-3 * len(table)}
    assert reader.read(context) == pytest.approx(len(under) / 2)
