"""The program's names for its own step and host loop
(telemetry/layers.py): scopes in the lowered step, the table
`op_layers` reads from the compiled HLO, the always-on `LoopStats`, and
the host spans a `jax.profiler` session records."""
import glob
import json
import threading
import time

import jax
import numpy as np
import optax
import pytest

from geomx_tpu.compression.bisparse import BiSparseCompressor
from geomx_tpu.ops.dispatch import kernels
from geomx_tpu.config import GeoConfig
from geomx_tpu.data.datasets import load_dataset
from geomx_tpu.data.loader import GeoDataLoader
from geomx_tpu.models import GeoCNN
from geomx_tpu.sync import FSA
from geomx_tpu.telemetry import layers
from geomx_tpu.topology import HiPSTopology
from geomx_tpu.train import Trainer
from geomx_tpu.utils.profiler import get_profiler

STEP_SCOPES = ("step/forward_backward", "step/sync_grads",
               "step/optimizer", "step/metrics", "compress/flatten",
               "compress/unflatten", "dc_allreduce/bucket0",
               "collective/worker", "collective/dc")
BSC_SCOPES = ("compress/boundary", "bsc/select_pack", "compress/exchange",
              "compress/merge", "bsc/scatter_add")


def _trainer(compression: str, parties=2, workers=2, **config):
    topo = HiPSTopology(num_parties=parties, workers_per_party=workers)
    cfg = GeoConfig(num_parties=parties, workers_per_party=workers,
                    **config)
    dc = BiSparseCompressor(0.01) if compression == "bsc" else None
    return Trainer(GeoCNN(num_classes=10), topo, optax.adam(1e-3),
                   sync=FSA(dc_compressor=dc, bucket_bytes=64 * 1024),
                   config=cfg)


@pytest.fixture(scope="module")
def data():
    return load_dataset("synthetic", synthetic_train_n=256)


def _first_batch(trainer, data, batch=8):
    loader = trainer.make_loader(data["train_x"], data["train_y"], batch)
    return next(iter(loader.epoch(0, prefetch=0)))


@pytest.mark.parametrize("compression", ["dense", "bsc"])
def test_step_hlo_holds_the_scopes_and_the_table_reads_them(compression,
                                                            data):
    trainer = _trainer(compression)
    state = trainer.init_state(jax.random.PRNGKey(0), data["train_x"][:2])
    xb, yb = _first_batch(trainer, data)
    # the chip's path (fused select/pack, scatter-add and bucket copies)
    # in Pallas interpret mode
    with kernels("interpret"):
        lowered = trainer.train_step.lower(state, xb, yb).as_text(
            debug_info=True)
        table = trainer.step_layers(state, xb, yb)
    expected = STEP_SCOPES + (BSC_SCOPES if compression == "bsc" else ())
    missing = [s for s in expected if s + "/" not in lowered]
    assert not missing, missing

    ops = table["ops"].values()
    assert table["instructions"] == len(table["ops"]) > 50
    directions = {v.direction for v in ops}
    assert {"forward", "backward"} <= directions
    assert any(v.scope == layers.OPTIMIZER for v in ops)
    named = [v for v in ops if v.scope is not None]
    assert len(named) == table["instructions"] - table["unnamed"]
    # of the instructions that carry an op name at all (the rest the
    # compiler made), under 5% sit under no scope of the vocabulary
    assert sum(1 for v in named if not v.scope) < 0.05 * len(named)
    assert table["unscoped"] == sum(1 for v in ops if not v.scope)
    engine = {v.scope for v in ops
              if v.scope and v.scope.startswith(layers.SYNC_GRADS)}
    assert any("collective/dc" in s for s in engine)
    if compression == "bsc":
        layers_seen = {v.layer for v in ops}
        assert {"compression engine", "kernels",
                "collectives / mesh"} <= layers_seen
        assert any(s.endswith("bsc/select_pack") for s in engine)
        assert any("compress/merge" in s for s in engine)


def test_classify_op_name_and_op_layers_by_hand():
    c = layers.classify_op_name
    assert c("jit(step)/step/forward_backward/jvp()/dot_general") == (
        "step/forward_backward", "step program", "forward", "first")
    assert c("jit(s)/shard_map/step/forward_backward/transpose(jvp())/"
             "transpose") == ("step/forward_backward", "step program",
                              "backward", "backward")
    assert c("jit(s)/step/sync_grads/dc_allreduce/bucket12/bsc/select_pack/"
             "pallas_call") == (
        "step/sync_grads/dc_allreduce/bucket12/bsc/select_pack", "kernels",
        None, None)
    assert c("jit(s)/shard_map/add") == layers.UNSCOPED
    hlo = """HloModule jit_s, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

%fused_computation (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %add.1 = f32[4]{0} add(%param_0, %param_0), metadata={op_name="jit(s)/step/optimizer/add"}
}

%body (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]{0}) parameter(0)
  %gte = f32[4]{0} get-tuple-element(%p), index=1
  %neg.7 = f32[4]{0} negate(%gte), metadata={op_name="jit(s)/step/sync_grads/compress/boundary/neg"}
  ROOT %t = (s32[], f32[4]{0}) tuple(%gte, %neg.7)
}

%cond (p.1: (s32[], f32[4])) -> pred[] {
  %p.1 = (s32[], f32[4]{0}) parameter(0)
  ROOT %lt = pred[] constant(false)
}

ENTRY %main (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %fusion.3 = f32[4]{0:T(128)} fusion(%a), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(s)/step/optimizer/add"}
  %copy.2 = f32[4]{0} copy(%fusion.3)
  %while.1 = (s32[], f32[4]{0}) while(%copy.2), condition=%cond, body=%body
  ROOT %bsc_select_pack.5 = f32[4]{0} custom-call(%copy.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(s)/step/sync_grads/dc_allreduce/bucket0/bsc/select_pack/pallas_call"}
}
"""
    table = layers.op_layers(hlo)
    assert set(table) == {"fusion.3", "copy.2", "while.1", "neg.7",
                          "bsc_select_pack.5"}
    assert table["fusion.3"].scope == "step/optimizer"
    assert table["copy.2"] == layers.UNNAMED
    assert table["neg.7"].layer == "compression engine"
    assert table["bsc_select_pack.5"].layer == "kernels"
    assert layers.op_layers(hlo.splitlines()) == table


def test_an_unnamed_instruction_in_a_loop_takes_the_loops_scope():
    """The compiler's own copies inside a scan's body carry no op_name:
    they are the loop's cost and go where the `while` goes, through a
    nested loop too; at entry level, and in the body of a loop that has
    no name itself, they stay unnamed."""
    hlo = """HloModule jit_s

%inner (q: (s32[], f32[4])) -> (s32[], f32[4]) {
  %q = (s32[], f32[4]{0}) parameter(0)
  %g = f32[4]{0} get-tuple-element(%q), index=1
  %copy-start.9 = (f32[4]{0:S(1)}, f32[4]{0}, u32[]) copy-start(%g)
  %copy-done.9 = f32[4]{0:S(1)} copy-done(%copy-start.9)
  ROOT %t.1 = (s32[], f32[4]{0}) tuple(%g, %copy-done.9)
}

%body (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]{0}) parameter(0)
  %copy.4 = (s32[], f32[4]{0}) copy(%p)
  %while.2 = (s32[], f32[4]{0}) while(%copy.4), condition=%cond, body=%inner, metadata={op_name="jit(s)/step/forward_backward/transpose(jvp(mixer))/kda/scan/while"}
  %while.3 = (s32[], f32[4]{0}) while(%copy.4), condition=%cond, body=%bare
  ROOT %t = (s32[], f32[4]{0}) tuple(%while.2, %while.3)
}

%bare (r: (s32[], f32[4])) -> (s32[], f32[4]) {
  %r = (s32[], f32[4]{0}) parameter(0)
  ROOT %copy.6 = (s32[], f32[4]{0}) copy(%r)
}

%lonely (r.1: (s32[], f32[4])) -> (s32[], f32[4]) {
  %r.1 = (s32[], f32[4]{0}) parameter(0)
  ROOT %copy.8 = (s32[], f32[4]{0}) copy(%r.1)
}

%cond (p.1: (s32[], f32[4])) -> pred[] {
  %p.1 = (s32[], f32[4]{0}) parameter(0)
  ROOT %lt = pred[] constant(false)
}

ENTRY %main (a: (s32[], f32[4])) -> (s32[], f32[4]) {
  %a = (s32[], f32[4]{0}) parameter(0)
  %copy.1 = (s32[], f32[4]{0}) copy(%a)
  %while.5 = (s32[], f32[4]{0}) while(%copy.1), condition=%cond, body=%lonely
  ROOT %while.1 = (s32[], f32[4]{0}) while(%while.5), condition=%cond, body=%body, metadata={op_name="jit(s)/step/forward_backward/jvp(mixer)/while"}
}
"""
    table = layers.op_layers(hlo)
    outer = ("step/forward_backward", "step program", "forward", "first")
    assert table["while.1"] == table["copy.4"] == outer
    assert table["while.3"] == table["copy.6"] == outer
    scan = ("step/forward_backward/kda/scan", "kernels", "backward",
            "backward")
    assert table["while.2"] == scan
    assert table["copy-start.9"] == table["copy-done.9"] == scan
    assert table["copy.1"] == table["while.5"] == table["copy.8"] \
        == layers.UNNAMED


class _SleepyLoader(GeoDataLoader):
    """Sleeps before every fourth batch: steps 3, 7, 11, ..."""

    def _batches(self, epoch):
        for i, batch in enumerate(super()._batches(epoch)):
            if i % 4 == 3:
                time.sleep(0.05)
            yield batch


class _Leave(Exception):
    pass


def _phases_fill_the_wall(stats):
    """`wall_s` closes with the last phase, so what it holds beyond the
    phases is the loop's own statements between them: milliseconds,
    whatever the window's length and the machine's load, and less than
    one of the loader's sleeps, so a wait that no phase took shows."""
    phased = sum(p["total_s"] for p in stats.phases.values())
    assert phased <= stats.wall_s
    assert stats.wall_s - phased < 0.04


def test_loop_stats_hold_the_loaders_sleeps_and_survive_log_fn(data):
    # prefetch 0: the loader assembles in the loop's own thread, so each
    # sleep is a wait of fit/next_batch
    trainer = _trainer("dense", parties=1, workers=2, prefetch=0)
    state = trainer.init_state(jax.random.PRNGKey(0), data["train_x"][:2])
    loader = _SleepyLoader(data["train_x"], data["train_y"],
                           trainer.topology, 8,
                           sharding=trainer._batch_sharding)
    assert loader.steps_per_epoch == 16
    state, _ = trainer.fit(state, loader, epochs=1, log_every=4,
                           log_fn=lambda _line: None)   # compiles
    state, _ = trainer.fit(state, loader, epochs=1, log_every=4,
                           log_fn=lambda _line: None)
    stats = trainer.loop_stats
    assert stats is layers.last_loop_stats() and stats.steps == 16
    phases = stats.phases
    assert set(phases) == set(layers.FIT_PHASES)
    assert phases["fit/next_batch"]["count"] == 17    # 16 batches + the end
    assert phases["fit/dispatch"]["count"] == 16
    assert phases["fit/log_fn"]["count"] == 4
    assert phases["fit/eval"]["count"] == 0
    _phases_fill_the_wall(stats)
    waits = phases["fit/next_batch"]
    assert 4 * 0.05 <= waits["total_s"] < 4 * 0.05 + 0.1
    assert waits["max_s"] >= 0.05 and waits["max_step"] % 4 == 3

    # a fit left by its log_fn (the chip benchmark ends its window so)
    # leaves the counters whole, and the accessor points at them
    seen = []

    def leave_at_second(line):
        seen.append(line)
        if len(seen) == 2:
            raise _Leave

    with pytest.raises(_Leave):
        trainer.fit(state, loader, epochs=1, log_every=4,
                    log_fn=leave_at_second)
    left = layers.last_loop_stats()
    assert left is trainer.loop_stats and left is not stats
    assert left.steps == 8 and left.phases["fit/log_fn"]["count"] == 2
    assert left.phases["fit/next_batch"]["count"] == 8
    _phases_fill_the_wall(left)
    assert left.as_dict()["phases"]["fit/dispatch"]["count"] == 8


def test_fit_phase_seconds_reach_the_registry_when_telemetry_is_on(data):
    from geomx_tpu.telemetry import get_registry, reset_registry
    reset_registry()
    trainer = _trainer("dense", parties=1, workers=2, telemetry=True)
    state = trainer.init_state(jax.random.PRNGKey(0), data["train_x"][:2])
    loader = trainer.make_loader(data["train_x"], data["train_y"], 8)
    trainer.fit(state, loader, epochs=1, log_every=4,
                log_fn=lambda _line: None)
    fam = get_registry().get("geomx_fit_phase_seconds")
    got = {labels: child.value for labels, child in fam.children()}
    assert got[("fit/dispatch",)] > 0
    assert set(p for (p,) in got) == {*layers.FIT_PHASES, layers.DRAINED}
    reset_registry()


def test_profiler_session_records_fit_spans_with_step(tmp_path, data):
    trainer = _trainer("dense", parties=1, workers=2)
    state = trainer.init_state(jax.random.PRNGKey(0), data["train_x"][:2])
    loader = trainer.make_loader(data["train_x"][:64], data["train_y"][:64],
                                 8)
    state, _ = trainer.fit(state, loader, epochs=1, log_every=2,
                           log_fn=lambda _line: None)   # compiles
    prof = get_profiler()
    prof.reset()
    assert not prof.running
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        trainer.fit(state, loader, epochs=1, log_every=2,
                    log_fn=lambda _line: None)
    finally:
        jax.profiler.stop_trace()
    # the host profiler was off: no Chrome event, whatever the session saw
    assert prof.aggregate_stats() == {}
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                         / "*.xplane.pb"))[0]
    spans = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("fit/", "train/step", "loader/")):
                    spans.setdefault(ev.name, []).append(
                        {k: v for k, v in ev.stats})
    steps = loader.steps_per_epoch
    assert sorted(s["step"] for s in spans["fit/dispatch"]) == list(
        range(steps))
    assert sorted(s["step"] for s in spans["train/step"]) == list(
        range(steps))
    assert len(spans["fit/next_batch"]) == steps + 1
    assert len(spans["fit/log_fn"]) == steps // 2
    assert len(spans["loader/assemble"]) == steps
    assert len(spans["loader/device_put"]) == steps
    assert spans["loader/epoch_start"] == [{"epoch": 0}]


def test_step_signature_lets_a_fresh_trainer_make_the_table(data):
    trainer = _trainer("dense", parties=1, workers=2)
    state = trainer.init_state(jax.random.PRNGKey(0), data["train_x"][:2])
    loader = trainer.make_loader(data["train_x"][:32], data["train_y"][:32],
                                 8)
    trainer.fit(state, loader, epochs=1, log_fn=lambda _line: None)
    signature = layers.last_step_signature()
    assert signature is trainer._step_args
    shapes = jax.tree.leaves(signature)
    assert all(isinstance(s, jax.ShapeDtypeStruct) for s in shapes)
    assert signature[1].shape == (1, 2, 8, 32, 32, 3)
    assert signature[1].sharding == trainer._batch_sharding
    fresh = _trainer("dense", parties=1, workers=2)
    table = fresh.step_layers(*signature)
    assert table["instructions"] > 50 and table["seconds"] > 0
    assert np.isfinite(table["unscoped"])


# --------------------------------------------------------------------------
# the lifecycle record: CompileLog and Lifecycle
# --------------------------------------------------------------------------

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
HIT = "/jax/compilation_cache/cache_hits"
MISS = "/jax/compilation_cache/cache_misses"
RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
SAVED = "/jax/compilation_cache/compile_time_saved_sec"


def _program(log, name, trace_s, lower_s, backend_s, cache=None,
             retrieval_s=0.0, inner=()):
    """The events JAX records for one program, in JAX's order: the nested
    traces end first, the function is `f` while traced and `jit(f)`
    afterwards, the cache's nameless events fall inside the backend
    interval, whose own event comes last."""
    for inner_name, secs in inner:
        log.on_duration(TRACE, secs, fun_name=inner_name)
    log.on_duration(TRACE, trace_s, fun_name=name)
    log.on_duration(LOWER, lower_s, fun_name=f"jit({name})")
    log.on_event("/jax/compilation_cache/compile_requests_use_cache")
    if cache == "hit":
        log.on_event(HIT)
        log.on_duration(SAVED, 3.0)
        log.on_duration(RETRIEVAL, retrieval_s)
    elif cache == "miss":
        log.on_event(MISS)
    log.on_duration(BACKEND, backend_s, fun_name=f"jit({name})")


def test_compile_log_sums_by_function_from_synthetic_events():
    log = layers.CompileLog()
    _program(log, "step", 2.0, 0.5, 7.0, inner=[("add", 0.25), ("add", 0.5)])
    _program(log, "step", 1.0, 0.25, 3.0)
    _program(log, "init", 0.125, 0.0625, 1.0)
    # a function traced and lowered ahead of time, never compiled
    log.on_duration(TRACE, 4.0, fun_name="lowered_only")
    log.on_duration(LOWER, 2.0, fun_name="jit(lowered_only)")
    step = log.by_fun["step"]
    assert (step["traces"], step["lowers"], step["compiles"]) == (2, 2, 2)
    assert (step["trace_s"], step["lower_s"], step["backend_s"]) == \
        (3.0, 0.75, 10.0)
    # a nested trace is in the summary and in no occurrence
    assert log.by_fun["add"] == {**layers._zero_program(), "traces": 2,
                                 "trace_s": 0.75}
    assert [o["fun_name"] for o in log.occurrences] == ["step", "step",
                                                        "init"]
    assert log.occurrences[0]["trace_s"] == 2.0
    assert log.occurrences[1]["lower_s"] == 0.25
    assert log.compiles == 3 and log.snapshot()["compiles"] == 3
    # nested seconds once: the totals' trace_s is the occurrences'
    assert log.totals["trace_s"] == 3.125
    assert log.totals["backend_s"] == 11.0
    assert log.by_fun["lowered_only"]["compiles"] == 0
    out = log.as_dict(top=2)
    assert list(out["by_fun"]) == ["step", "lowered_only"]
    assert out["functions"] == 4 and len(out["occurrences"]) == 3
    assert out["last"]["fun_name"] == "init"
    # a program JAX had the jaxpr of: no trace event, only the backend's
    log.on_duration(BACKEND, 0.5, fun_name="jit(step)")
    assert log.occurrences[-1]["trace_s"] == 0.0
    # other events and nameless durations are not the log's
    log.on_duration("/jax/something/else", 1.0, fun_name="step")
    log.on_duration(BACKEND, 1.0)
    assert log.compiles == 4
    log.clear()
    assert log.compiles == 0 and not log.occurrences and not log.by_fun


@pytest.mark.parametrize("cache,retrieval_s", [("hit", 0.75), ("miss", 0.0),
                                               (None, 0.0)])
def test_compile_log_attaches_the_nameless_cache_event(cache, retrieval_s):
    log = layers.CompileLog()
    _program(log, "before", 0.1, 0.1, 0.1)
    _program(log, "step", 1.0, 1.0, 2.0, cache=cache,
             retrieval_s=retrieval_s)
    _program(log, "after", 0.1, 0.1, 0.1)
    before, step, after = log.occurrences
    assert step["cache"] == (cache or "none")
    assert step["retrieval_s"] == retrieval_s
    # the event is used up by the compile that enclosed it
    assert before["cache"] == after["cache"] == "none"
    assert after["retrieval_s"] == 0.0
    assert log.by_fun["step"]["cache_hits"] == (cache == "hit")
    assert log.totals["cache_misses"] == (cache == "miss")
    assert log.totals["retrieval_s"] == retrieval_s


def test_a_cache_event_on_another_thread_is_not_this_threads():
    log = layers.CompileLog()
    other = threading.Thread(target=lambda: (log.on_event(HIT),
                                             log.on_duration(RETRIEVAL, 9.0)))
    other.start()
    other.join(timeout=10)
    assert not other.is_alive()
    _program(log, "step", 1.0, 1.0, 2.0)
    assert log.occurrences[0]["cache"] == "none"
    assert log.occurrences[0]["retrieval_s"] == 0.0


def test_compile_log_takes_phase_and_step_from_the_open_phase():
    log = layers.CompileLog()
    assert layers.open_phase() == ("outside", None)
    _program(log, "eager", 0.1, 0.1, 0.1)
    life = layers.Lifecycle(log)
    with life.span("setup/init_state"):
        _program(log, "zeros", 0.1, 0.1, 0.1)
        with life.span("setup/model_init"):
            _program(log, "init", 0.1, 0.1, 0.1)
        _program(log, "ones", 0.1, 0.1, 0.1)
    stats = layers.LoopStats()
    stats.step = 41
    with stats.phase("fit/dispatch"):
        _program(log, "step", 0.1, 0.1, 0.1)
    with stats.phase("fit/eval"):
        _program(log, "run", 0.1, 0.1, 0.1)
    assert layers.open_phase() == ("outside", None)
    assert [(o["fun_name"], o["phase"], o["step"]) for o in log.occurrences] \
        == [("eager", "outside", None), ("zeros", "setup/init_state", 0),
            ("init", "setup/model_init", 0), ("ones", "setup/init_state", 0),
            ("step", "fit/dispatch", 41), ("run", "fit/eval", 41)]
    # a phase left by an exception is closed all the same
    with pytest.raises(_Leave):
        with stats.phase("fit/log_fn"):
            raise _Leave
    assert layers.open_phase() == ("outside", None)


def test_compile_log_stops_growing_at_its_bound():
    log = layers.CompileLog(keep=8)
    for i in range(50):
        _program(log, f"f{i}", 1.0, 1.0, 1.0, cache="hit", retrieval_s=0.5)
    assert len(log.occurrences) == 8 and log.dropped == 42
    assert [o["fun_name"] for o in log.occurrences] == \
        [f"f{i}" for i in range(8)]
    # the sums go on, the names beyond the bound under one
    assert log.compiles == 50 and log.totals["backend_s"] == 50.0
    assert len(log.by_fun) == 9 and log.by_fun["(other)"]["compiles"] == 42
    assert log.last["fun_name"] == "f49"
    # traces and lowerings that never reach a compile are bounded too
    for i in range(500):
        log.on_duration(TRACE, 1.0, fun_name=f"g{i}")
        log.on_duration(LOWER, 1.0, fun_name=f"jit(g{i})")
    assert len(log._thread()["traced"]) == 9
    assert len(log._thread()["lowered"]) <= 64
    assert len(log.by_fun) == 9
    assert log.as_dict()["totals"]["traces"] == 50 + 500
    assert log.by_fun["(other)"]["traces"] == 42 + 500


def test_marks_keep_first_last_and_max_of_an_injected_source():
    readings = iter([
        {"bytes_in_use": 10, "bytes_reserved": 5, "peak_bytes_in_use": 10},
        {"bytes_in_use": 70, "bytes_reserved": 2, "peak_bytes_in_use": 90},
        {"bytes_in_use": 30, "bytes_reserved": 9, "peak_bytes_in_use": 90},
        {"bytes_in_use": 40, "bytes_reserved": 1, "peak_bytes_in_use": 95},
    ])
    life = layers.Lifecycle(memory_source=lambda: next(readings))
    for step in (4, 8, 12):
        life.mark("fit/end", step)
    rec = life.marks["fit/end"]
    assert rec["count"] == 3
    assert (rec["first"]["bytes_in_use"], rec["first"]["step"]) == (10, 4)
    assert (rec["last"]["bytes_in_use"], rec["last"]["step"]) == (30, 12)
    assert rec["max"] == {"bytes_in_use": 70, "bytes_reserved": 9,
                          "peak_bytes_in_use": 90}
    assert rec["first"]["t"] <= rec["last"]["t"]
    # the step's scratch space: reserved at the boundary less before
    assert life.step_reserved_bytes() is None
    life.marks["fit/first_dispatch"] = rec
    life.first_boundary(1)
    assert life.step_reserved_bytes() == 1 - 5
    assert life.first_boundary_t == life.marks["fit/first_boundary"][
        "first"]["t"]
    out = life.as_dict()
    assert out["marks"]["fit/end"]["max"]["bytes_reserved"] == 9
    assert out["step_reserved_bytes"] == -4 and "programs" not in out


@pytest.mark.parametrize("source", [None, lambda: None],
                         ids=["no_source", "backend_without_stats"])
def test_marks_read_none_without_allocator_statistics(source):
    life = layers.Lifecycle(memory_source=source)
    now = life.mark("fit/first_dispatch")
    life.first_boundary(0)
    assert now["bytes_in_use"] is None and now["t"] > 0
    assert life.marks["fit/first_dispatch"]["max"] == dict.fromkeys(
        ("bytes_in_use", "bytes_reserved", "peak_bytes_in_use"))
    assert life.step_reserved_bytes() is None
    assert life.first_boundary_t is not None


def test_fullest_device_stats_picks_by_in_use_plus_reserved():
    class Device:
        def __init__(self, stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats

    a = {"bytes_in_use": 5, "bytes_reserved": 1, "peak_bytes_in_use": 50}
    b = {"bytes_in_use": 3, "bytes_reserved": 9, "peak_bytes_in_use": 4}
    assert layers.fullest_device_stats([Device(a), Device(None),
                                        Device(b)]) is b
    assert layers.fullest_device_stats([Device(None)]) is None
    # the CPU backend keeps none
    assert layers.fullest_device_stats(jax.devices()) is None


def test_a_tiny_fit_records_set_up_once_a_trainer(data):
    log = layers.compile_log()
    assert log is layers.compile_log()
    log.clear()
    trainer = _trainer("dense", parties=1, workers=2)
    life = trainer.lifecycle
    assert life.programs is log and life.step_fun == "_device_step"
    assert life.spans["setup/build"]["count"] == 1
    state = trainer.init_state(jax.random.PRNGKey(0), data["train_x"][:2])
    for name in ("setup/init_state", "setup/model_init", "setup/state_init",
                 "setup/replicate"):
        assert life.spans[name]["count"] == 1, name
        assert 0 < life.spans[name]["seconds"] == life.spans[name]["total_s"]
    inner = sum(life.spans[name]["seconds"] for name in
                ("setup/model_init", "setup/state_init", "setup/replicate"))
    assert inner <= life.spans["setup/init_state"]["seconds"]
    assert life.marks["setup/init_state:begin"]["first"]["t"] <= \
        life.spans["setup/init_state"]["begin"]
    assert life.marks["setup/init_state:end"]["count"] == 1
    # init_state's programs carry its phases (the eager one-offs of
    # setup/state_init only where this process has not run their shapes)
    phases = {o["phase"] for o in log.occurrences}
    assert "setup/model_init" in phases
    assert phases <= {"setup/model_init", "setup/state_init",
                      "setup/replicate", "setup/init_state", "outside"}
    assert "fit/first_dispatch" not in life.spans

    loader = trainer.make_loader(data["train_x"][:64], data["train_y"][:64],
                                 8)
    for fits in (1, 2):
        state, _ = trainer.fit(state, loader, epochs=1, log_every=4,
                               log_fn=lambda _line: None)
        # once a trainer, not once a fit
        assert life.spans["fit/first_dispatch"]["count"] == 1
        assert life.marks["fit/first_dispatch"]["count"] == 1
        assert life.marks["fit/first_boundary"]["count"] == 1
        assert life.marks["fit/end"]["count"] == fits
    assert life is layers.last_lifecycle()
    # the placed state's bytes a chip, by class, as the first step got
    # them: Adam holds m and v
    sizes = life.state_bytes
    assert set(sizes) == {"params", "opt_state", "sync_state", "model_state"}
    assert sizes["params"] > 0
    assert 2 * sizes["params"] <= sizes["opt_state"] < 2.01 * sizes["params"]
    assert life.marks["fit/first_boundary"]["first"]["step"] == 1
    assert life.marks["fit/end"]["last"]["step"] == loader.steps_per_epoch
    step = life.step_program()
    assert step["fun_name"] == "_device_step"
    assert (step["phase"], step["step"]) == ("fit/first_dispatch", 0)
    assert step["trace_s"] > 0 and step["lower_s"] > 0
    assert step["backend_s"] > 0
    assert step["cache"] in ("hit", "miss", "none")
    assert step["trace_s"] + step["lower_s"] + step["backend_s"] <= \
        life.spans["fit/first_dispatch"]["seconds"]
    dispatch = life.spans["fit/first_dispatch"]
    assert dispatch["begin"] + dispatch["seconds"] <= life.first_boundary_t
    # set-up's programs: here everything is up to the first boundary
    assert all(o["t"] <= life.first_boundary_t for o in log.occurrences)
    out = life.as_dict()
    assert out["step_program"] == step
    assert out["programs"]["totals"]["compiles"] == log.compiles
    assert json.loads(json.dumps(out)) == out


def test_a_fit_left_by_log_fn_still_has_its_end_mark(data):
    trainer = _trainer("dense", parties=1, workers=2)
    state = trainer.init_state(jax.random.PRNGKey(0), data["train_x"][:2])
    loader = trainer.make_loader(data["train_x"][:64], data["train_y"][:64],
                                 8)

    def leave(_line):
        raise _Leave

    with pytest.raises(_Leave):
        trainer.fit(state, loader, epochs=1, log_every=3, log_fn=leave)
    end = trainer.lifecycle.marks["fit/end"]
    assert end["count"] == 1 and end["last"]["step"] == 3
    # the CPU syncs every step: the first step's results came at once
    assert trainer.lifecycle.marks["fit/first_boundary"]["first"]["step"] == 1
    assert layers.open_phase() == ("outside", None)


def test_a_recompile_is_one_occurrence_with_its_phase_and_step(data):
    log = layers.compile_log()
    trainer = _trainer("dense", parties=1, workers=2)
    state = trainer.init_state(jax.random.PRNGKey(0), data["train_x"][:2])
    x, y = data["train_x"], data["train_y"]
    state, _ = trainer.fit(state, trainer.make_loader(x[:64], y[:64], 8),
                           epochs=1, log_fn=lambda _line: None)
    log.clear()
    # a loader whose third batch has another shape: the operator's "which
    # step recompiled"
    small = list(trainer.make_loader(x[:16], y[:16], 4).epoch(0, prefetch=0))
    usual = list(trainer.make_loader(x[:64], y[:64], 8).epoch(0, prefetch=0))

    class Mixed:
        steps_per_epoch = 4

        def epoch(self, _epoch, prefetch=0):
            return iter(usual[:2] + small[:1] + usual[2:3])

    state, _ = trainer.fit(state, Mixed(), epochs=1,
                           log_fn=lambda _line: None)
    again = [o for o in log.occurrences if o["fun_name"] == "_device_step"]
    assert len(again) == 1
    assert (again[0]["phase"], again[0]["step"]) == ("fit/dispatch", 2)
    assert again[0]["trace_s"] > 0 and again[0]["backend_s"] > 0
    assert log.last is log.occurrences[-1]
    # not the trainer's first dispatch: the span stays the first fit's
    assert trainer.lifecycle.spans["fit/first_dispatch"]["count"] == 1
    assert trainer.lifecycle.step_program() is None    # cleared above


def test_the_steps_jaxpr_is_unchanged_by_the_record(data):
    trainer = _trainer("dense", parties=1, workers=2)
    state = trainer.init_state(jax.random.PRNGKey(0), data["train_x"][:2])
    xb, yb = _first_batch(trainer, data)
    plain = jax.make_jaxpr(trainer.train_step)(state, xb, yb)
    text = trainer.train_step.lower(state, xb, yb).as_text(debug_info=True)
    stats = layers.LoopStats()
    with stats.phase("fit/dispatch"), \
            trainer.lifecycle.first_dispatch(state, 2, 0):
        inside = jax.make_jaxpr(trainer.train_step)(state, xb, yb)
        text_inside = trainer.train_step.lower(state, xb, yb).as_text(
            debug_info=True)
    assert str(inside) == str(plain)
    assert text_inside == text
    assert "fit/first_dispatch" not in text and "setup/" not in text


def test_memory_gauges_come_from_the_record_without_a_second_compile(data):
    from geomx_tpu.telemetry import get_registry, reset_registry
    reset_registry()
    log = layers.compile_log()
    trainer = _trainer("dense", parties=1, workers=2, telemetry=True)
    reserved = iter([100, 100, 100, 4196, 4196, 4196])
    trainer.lifecycle.memory_source = lambda: {
        "bytes_in_use": 1, "bytes_reserved": next(reserved),
        "peak_bytes_in_use": 2}
    state = trainer.init_state(jax.random.PRNGKey(0), data["train_x"][:2])
    loader = trainer.make_loader(data["train_x"][:32], data["train_y"][:32],
                                 8)
    log.clear()
    trainer.fit(state, loader, epochs=1, log_every=2,
                log_fn=lambda _line: None)
    fam = get_registry().get("geomx_step_memory_bytes")
    got = {labels[0]: child.value for labels, child in fam.children()}
    assert got["compiled_step"] == 4096.0
    assert got["params"] == trainer.lifecycle.state_bytes["params"] > 0
    assert set(got) == {"params", "opt_state", "sync_state", "model_state",
                        "compiled_step"}
    # the step was asked of XLA once: no second lowering for the gauges
    assert log.by_fun["_device_step"]["compiles"] == 1
    assert log.by_fun["_device_step"]["lowers"] == 1
    reset_registry()


def test_setup_scopes_belong_to_the_host_loop_layer():
    for name in layers.SETUP_SPANS + ("setup/anything",):
        assert layers.layer_of(name) == "entry / host loop", name
    assert set(layers.MEMORY_MARKS) >= {"fit/first_boundary", "fit/end"}
