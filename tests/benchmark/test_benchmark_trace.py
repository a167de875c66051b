"""The trace reducer on the small trace recorded on a TPU v5 lite
(benchmark/tools/record_trace.py): five calls of one jitted step of two
2048^3 bf16 matmuls, a 30 ms host sleep between calls 2 and 3."""
import os

import pytest

from bench_paths import ROOT

from benchmark import trace_reduce

TRACE = os.path.join(ROOT, "benchmark", "testdata", "small.xplane.pb")


@pytest.fixture(scope="module")
def summary():
    return trace_reduce.reduce_trace(TRACE)


def test_steps_and_busy_time(summary):
    assert summary["chips"] == 1 and summary["steps"] == 5
    assert summary["step_module"] == "jit_small_step"
    # read off the trace by hand: each call is busy for 182.2 us
    assert summary["busy_s_busiest"] == pytest.approx(911.209e-6, rel=1e-6)
    assert summary["busy_s_mean"] == summary["busy_s_busiest"]
    # first step program's start to the last one's end
    assert summary["window_s"] == pytest.approx(35.682070e-3, rel=1e-7)
    assert 1 - summary["busy_s_busiest"] / summary["window_s"] > 0.97


def test_time_per_kernel(summary):
    # five executions of each fusion, 91.1 us and 91.2 us each: a 2048^3
    # bf16 matmul at 188 TFLOP/s of the chip's 197
    assert summary["by_family_s"]["fusion"] == pytest.approx(455.054e-6, rel=1e-6)
    assert summary["by_family_s"]["convert_reduce_fusion"] == pytest.approx(
        456.075e-6, rel=1e-6)
    flops = 2 * 2048 ** 3
    assert 0.9 < flops / (summary["by_family_s"]["fusion"] / 5) / 197e12 < 1.0
    assert trace_reduce.family_time_s(summary, ("fusion", "convert")) == \
        pytest.approx(911.129e-6, rel=1e-6)
    assert trace_reduce.family_time_s(summary, ("bsc_select_pack",)) == 0.0


def test_longest_gap_is_charged_to_what_the_host_was_doing(summary):
    name, seconds, at = summary["idle_gaps"][0]
    assert name == "bench/host_sleep"
    assert seconds == pytest.approx(31.782083e-3, rel=1e-6)
    # after the second call's end, 1.344538 ms into the window
    assert at == pytest.approx(1.344538e-3, rel=1e-6)
    assert summary["idle_gaps"][1][0] == "bench/segment"
    out = trace_reduce.breakdown(summary)
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10
    assert out["device_ops"][0][0] == "convert_reduce_fusion"
    assert out["idle_gaps"][0] == [name, seconds]


def test_names_and_unions():
    assert trace_reduce.op_name(
        "%bsc_select_pack.15 = (f32[8,128]{1,0}) custom-call(f32[1] %x)") \
        == "bsc_select_pack.15"
    assert trace_reduce.op_family("bsc_select_pack.15") == "bsc_select_pack"
    assert trace_reduce.op_family("copy.2") == "copy"
    assert trace_reduce.union_ns([(0, 10), (5, 20), (30, 40)]) == 30
    assert trace_reduce.merged([(5, 20), (0, 10), (30, 40)]) == [[0, 20], [30, 40]]


def test_the_window_is_whole_step_programs_on_made_up_planes():
    chips = {"/device:TPU:0": {
        "XLA Ops": [("%copy.1 = f32[] copy()", 0.0, 40.0),        # before
                    ("%fusion.1 = f32[] fusion()", 100.0, 180.0),
                    ("%fusion.2 = f32[] fusion()", 230.0, 300.0),
                    ("%copy.1 = f32[] copy()", 900.0, 950.0)],    # after
        "XLA Modules": [("jit_put(7)", 0.0, 40.0),
                        ("jit_step(1)", 100.0, 200.0),
                        ("jit_step(1)", 220.0, 300.0),
                        ("jit_put(7)", 900.0, 950.0)]},
        "/device:TPU:1": {
        "XLA Ops": [("%fusion.1 = f32[] fusion()", 110.0, 160.0)],
        "XLA Modules": [("jit_step(1)", 110.0, 160.0)]}}
    s = trace_reduce.reduce_planes(chips, [])
    assert s["busiest_chip"] == "/device:TPU:0" and s["chips"] == 2
    assert s["step_module"] == "jit_step" and s["steps"] == 2
    assert s["window_s"] == pytest.approx(200e-9)
    assert s["busy_s_busiest"] == pytest.approx(150e-9)
    assert s["busy_s_mean"] == pytest.approx(100e-9)
    assert "copy" not in s["by_family_s"]
    assert s["idle_gaps"][0][1:] == [pytest.approx(50e-9), pytest.approx(80e-9)]
    with pytest.raises(ValueError):
        trace_reduce.reduce_planes({"/device:TPU:0": {"XLA Ops": []}}, [])


def test_an_events_own_time_is_its_span_less_what_is_nested_in_it():
    """A `while` encloses its body's ops on the `XLA Ops` line: summed by
    name, the own times give the busy time, the spans count the loop
    twice.  Made-up events: a loop of two iterations (a fusion and a
    copy each, a 5 ns hole in the second), an inner loop nested in the
    second iteration's fusion slot, and an op after the loop."""
    events = [("%while.1 = () while()", 100.0, 200.0),
              ("%fusion.1 = f32[] fusion()", 100.0, 130.0),
              ("%copy.2 = f32[] copy()", 130.0, 150.0),
              ("%while.7 = () while()", 150.0, 180.0),
              ("%fusion.9 = f32[] fusion()", 150.0, 175.0),
              ("%copy.2 = f32[] copy()", 180.0, 195.0),
              ("%reduce.3 = f32[] reduce()", 200.0, 240.0)]
    own = trace_reduce.own_ns(events[::-1])         # any order
    assert [(trace_reduce.op_name(t), ns) for t, ns in own] == [
        ("while.1", 5.0), ("fusion.1", 30.0), ("copy.2", 20.0),
        ("while.7", 5.0), ("fusion.9", 25.0), ("copy.2", 15.0),
        ("reduce.3", 40.0)]
    chips = {"/device:TPU:0": {
        "XLA Ops": events, "XLA Modules": [("jit_step(1)", 100.0, 240.0)]}}
    s = trace_reduce.reduce_planes(chips, [])
    assert s["busy_s_busiest"] == pytest.approx(140e-9)
    assert sum(s["by_op_s"].values()) == pytest.approx(140e-9)
    assert s["by_family_s"]["while"] == pytest.approx(10e-9)
    assert s["by_family_s"]["copy"] == pytest.approx(35e-9)
    # events that only touch are not nested
    flat = [("%a.1 = f32[] a()", 0.0, 10.0), ("%b.1 = f32[] b()", 10.0, 30.0)]
    assert [ns for _, ns in trace_reduce.own_ns(flat)] == [10.0, 20.0]


def test_a_scans_while_is_not_counted_twice_on_the_recorded_trace():
    """`record_trace.py scan` on a TPU v5 lite (PR 26): three calls of one
    jitted step that holds a matmul, a `lax.scan` of eight iterations and
    two reductions.  On the `XLA Ops` line each call's `%while` is one
    event of 743 us that encloses its body's 24 op events, so the 105
    events' spans sum to 4.790 ms where the device was busy for 2.560 ms:
    the per-op and per-family times are own times and sum to the busy
    time, the loop itself keeping 97 ns."""
    path = os.path.join(ROOT, "benchmark", "testdata", "scan.xplane.pb")
    chips, host = trace_reduce.read_planes(path)
    ops = chips["/device:TPU:0"]["XLA Ops"]
    loops = [b - a for text, a, b in ops
             if trace_reduce.op_name(text) == "while"]
    assert len(ops) == 105 and len(loops) == 3
    assert sum(loops) == pytest.approx(2230.029e3)
    assert sum(b - a for _, a, b in ops) == pytest.approx(4789.627e3)
    s = trace_reduce.reduce_planes(chips, host)
    assert s["steps"] == 3 and s["step_module"] == "jit_scan_step"
    assert s["busy_s_busiest"] == pytest.approx(2559.695e-6, rel=1e-9)
    assert sum(s["by_op_s"].values()) == pytest.approx(s["busy_s_busiest"],
                                                       rel=1e-9)
    assert sum(s["by_family_s"].values()) == pytest.approx(
        s["busy_s_busiest"], rel=1e-9)
    assert s["by_family_s"]["while"] == pytest.approx(97e-9)
    # the body's matmul, 24 executions of 90.468 us, and the one before the
    # loop's three
    assert s["by_family_s"]["convert_reduce_fusion"] == pytest.approx(
        2188.875e-6, rel=1e-6)
    assert s["by_family_s"]["fusion"] == pytest.approx(272.221e-6, rel=1e-6)
