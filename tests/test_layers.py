"""The program's names for its own step and host loop
(telemetry/layers.py): scopes in the lowered step, the table
`op_layers` reads from the compiled HLO, the always-on `LoopStats`, and
the host spans a `jax.profiler` session records."""
import glob
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
        "step/forward_backward", "step program", "forward")
    assert c("jit(s)/shard_map/step/forward_backward/transpose(jvp())/"
             "transpose") == ("step/forward_backward", "step program",
                              "backward")
    assert c("jit(s)/step/sync_grads/dc_allreduce/bucket12/bsc/select_pack/"
             "pallas_call") == (
        "step/sync_grads/dc_allreduce/bucket12/bsc/select_pack", "kernels",
        None)
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
    outer = ("step/forward_backward", "step program", "forward")
    assert table["while.1"] == table["copy.4"] == outer
    assert table["while.3"] == table["copy.6"] == outer
    scan = ("step/forward_backward/kda/scan", "kernels", "backward")
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
    assert set(p for (p,) in got) == set(layers.FIT_PHASES)
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
