"""The pass of every instruction under ``step/forward_backward``
(telemetry/layers.OpLayer.pass_: first forward, recomputed forward,
backward), the scopes ``block/norm`` and ``lm/embed`` of
``models/decoder.py`` in a tiny decoder's compiled step with
rematerialisation on and off, and ``LoopStats``' ``fit/drained``."""
import jax
import optax
import pytest

import decoder_checks as checks
import test_glm4_moe_lite
from geomx_tpu.config import GeoConfig
from geomx_tpu.data.datasets import load_dataset
from geomx_tpu.models import GeoCNN
from geomx_tpu.sync import FSA
from geomx_tpu.telemetry import layers
from geomx_tpu.topology import HiPSTopology
from geomx_tpu.train import Trainer

FB = "step/forward_backward"
REMATTED = "transpose(jvp(Lm))/layer2/checkpoint/rematted_computation/"


@pytest.mark.parametrize("op_name, want", [
    # the first pass of a rematerialised half: `checkpoint`, no transpose
    (f"jit(s)/{FB}/jvp(Lm)/layer2/checkpoint/ffn/block/norm/norm/mul",
     (f"{FB}/block/norm", "step program", "forward", layers.FIRST)),
    # its copy inside the backward: direction stays backward
    (f"jit(s)/{FB}/{REMATTED}ffn/core/ffn/mlp/dot_general",
     (f"{FB}/ffn/mlp", "step program", "backward", layers.RECOMPUTED)),
    # the backward proper of the same half
    (f"jit(s)/{FB}/transpose(jvp(Lm))/layer2/checkpoint/ffn/core/ffn/mlp/"
     "transpose",
     (f"{FB}/ffn/mlp", "step program", "backward", layers.BACKWARD)),
    # the name stack the backward of a whole-batch half really has: the
    # equation's own stack behind the transposed one
    (f"jit(s)/{FB}/transpose(jvp(Lm))/layer2/{FB}/jvp(Lm)/layer2/checkpoint/"
     "rematted_computation/ffn/block/norm/norm/rsqrt",
     (f"{FB}/{FB}/block/norm", "step program", "backward",
      layers.RECOMPUTED)),
    # the embedding's gradient is the lookup's scope, transposed
    (f"jit(s)/{FB}/transpose(jvp(Lm))/lm/embed/scatter-add",
     (f"{FB}/lm/embed", "step program", "backward", layers.BACKWARD)),
    # outside step/forward_backward there is no pass
    ("jit(s)/step/optimizer/checkpoint/rematted_computation/add",
     ("step/optimizer", "step program", None, None)),
])
def test_classify_op_name_tells_the_three_passes(op_name, want):
    assert layers.classify_op_name(op_name) == want


def test_a_compiler_made_instruction_in_a_recomputed_loop_takes_its_pass():
    """A `while` inside the recomputed forward (a scan's chunks) hands its
    pass to the copies the compiler schedules in its body; the sequences'
    loop around it holds recomputed forward and backward both and is the
    backward's."""
    hlo = """HloModule jit_s

%chunks (q: (s32[], f32[4])) -> (s32[], f32[4]) {
  %q = (s32[], f32[4]{0}) parameter(0)
  %g = f32[4]{0} get-tuple-element(%q), index=1
  %copy.9 = f32[4]{0} copy(%g)
  %dot.3 = f32[4]{0} negate(%copy.9), metadata={op_name="jit(s)/step/forward_backward/transpose(jvp(Lm))/mixer/checkpoint/rematted_computation/core/kda/scan/while/body/dot_general"}
  ROOT %t.1 = (s32[], f32[4]{0}) tuple(%g, %dot.3)
}

%sequences (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]{0}) parameter(0)
  %copy.4 = (s32[], f32[4]{0}) copy(%p)
  %while.2 = (s32[], f32[4]{0}) while(%copy.4), condition=%cond, body=%chunks, metadata={op_name="jit(s)/step/forward_backward/transpose(jvp(Lm))/mixer/checkpoint/rematted_computation/core/kda/scan/while"}
  %fusion.7 = f32[4]{0} fusion(%copy.4), kind=kLoop, calls=%fused, metadata={op_name="jit(s)/step/forward_backward/transpose(jvp(Lm))/mixer/checkpoint/core/kda/proj/mul"}
  ROOT %t = (s32[], f32[4]{0}) tuple(%while.2, %fusion.7)
}

%fused (a.1: f32[4]) -> f32[4] {
  %a.1 = f32[4]{0} parameter(0)
  ROOT %mul.1 = f32[4]{0} multiply(%a.1, %a.1)
}

%cond (p.1: (s32[], f32[4])) -> pred[] {
  %p.1 = (s32[], f32[4]{0}) parameter(0)
  ROOT %lt = pred[] constant(false)
}

ENTRY %main (a: (s32[], f32[4])) -> (s32[], f32[4]) {
  %a = (s32[], f32[4]{0}) parameter(0)
  ROOT %while.1 = (s32[], f32[4]{0}) while(%a), condition=%cond, body=%sequences, metadata={op_name="jit(s)/step/forward_backward/transpose(jvp(Lm))/mixer/while"}
}
"""
    table = layers.op_layers(hlo)
    bare = (FB, "step program", "backward", layers.BACKWARD)
    assert table["while.1"] == table["copy.4"] == bare
    scan = (f"{FB}/kda/scan", "kernels", "backward", layers.RECOMPUTED)
    assert table["while.2"] == table["copy.9"] == table["dot.3"] == scan
    assert table["fusion.7"] == (f"{FB}/kda/proj", "step program",
                                 "backward", layers.BACKWARD)
    assert {e.pass_ for e in table.values()} <= set(layers.PASSES)


@pytest.mark.parametrize("remat", [True, False], ids=["remat", "no-remat"])
def test_a_tiny_decoder_recomputes_only_when_it_rematerialises(remat):
    """The GLM tiny model (latent mixers, expert layers, one prediction
    module): `block/norm` and `lm/embed` name instructions either way, the
    module's nest inside its scopes, and an instruction of the recomputed
    pass exists exactly where `jax.checkpoint` wraps the halves."""
    built = checks.Built(test_glm4_moe_lite.FAMILY, remat=remat)
    table = layers.op_layers(built.compiled.as_text())
    under = [e for e in table.values() if e.direction]
    assert under and all(e.pass_ in layers.PASSES for e in under)
    assert all(e.pass_ is None for e in table.values() if not e.direction)
    assert all((e.direction == "forward") == (e.pass_ == layers.FIRST)
               for e in under)
    recomputed = [e for e in under if e.pass_ == layers.RECOMPUTED]
    assert bool(recomputed) == remat
    by_scope = {}
    for e in under:
        by_scope.setdefault(e.scope.replace(FB, "").strip("/"),
                            set()).add(e.pass_)
    for scope in ("block/norm", "lm/embed", "mtp/module/block/norm",
                  "mtp/module/mtp/combine/lm/embed"):
        assert {layers.FIRST, layers.BACKWARD} <= by_scope[scope], scope
    if remat:
        # a half's norm is computed again; the embedding is outside every
        # rematerialised half and never is
        assert layers.RECOMPUTED in by_scope["block/norm"]
        assert layers.RECOMPUTED not in by_scope["lm/embed"]
    # a mixer's own norms stay in its projection's scope
    assert not any("block/norm" in s and ("mla/proj" in s or "moe/" in s)
                   for s in by_scope)


def _boundary(stats, step, synced):
    stats.step = step
    with stats.phase("fit/next_batch"):
        pass
    with stats.phase("fit/dispatch"):
        pass
    if synced:
        with stats.phase("fit/log_sync"):
            pass
        with stats.phase("fit/log_fn"):
            pass


@pytest.mark.parametrize("log_every", [1, 4])
def test_drained_is_counted_once_a_boundary(log_every):
    """The loop of `Trainer.fit` by hand, as an accelerator runs it (the
    CPU backend syncs every step): one `fit/drained` a log boundary that a
    dispatch follows, from the sync's end to that dispatch's end."""
    stats = layers.LoopStats()
    for step in range(8):
        _boundary(stats, step, synced=(step + 1) % log_every == 0)
    boundaries = 8 // log_every
    assert stats.phases["fit/log_sync"]["count"] == boundaries
    # the last boundary has no dispatch behind it
    assert stats.drained["count"] == boundaries - 1
    assert stats.drained["max_step"] % log_every == 0
    assert 0.0 < stats.drained["max_s"] <= stats.drained["total_s"] \
        <= stats.wall_s
    assert stats.as_dict()[layers.DRAINED] == stats.drained


def test_fit_counts_drained_and_leaves_it_whole_when_log_fn_raises():
    data = load_dataset("synthetic", synthetic_train_n=128)
    topo = HiPSTopology(num_parties=1, workers_per_party=2)
    trainer = Trainer(GeoCNN(num_classes=10), topo, optax.adam(1e-3),
                      sync=FSA(bucket_bytes=64 * 1024),
                      config=GeoConfig(num_parties=1, workers_per_party=2))
    state = trainer.init_state(jax.random.PRNGKey(0), data["train_x"][:2])
    loader = trainer.make_loader(data["train_x"], data["train_y"], 8)
    assert loader.steps_per_epoch == 8
    state, _ = trainer.fit(state, loader, epochs=1, log_every=4,
                           log_fn=lambda _line: None)
    stats = trainer.loop_stats
    # on the CPU every step is synced, so every dispatch but the first
    # finds the device drained
    synced = stats.phases["fit/log_sync"]["count"]
    assert synced == 8 and stats.drained["count"] == synced - 1
    between = sum(stats.phases[p]["total_s"] for p in (
        "fit/next_batch", "fit/dispatch", "fit/log_fn"))
    assert 0.0 < stats.drained["total_s"] < between + 0.04

    class Leave(Exception):
        pass

    seen = []

    def leave_at_first(line):
        seen.append(line)
        raise Leave

    with pytest.raises(Leave):
        trainer.fit(state, loader, epochs=1, log_every=4,
                    log_fn=leave_at_first)
    left = layers.last_loop_stats()
    assert left is trainer.loop_stats and left.steps == 4
    assert left.phases["fit/log_sync"]["count"] == 4
    assert left.drained["count"] == 3
    assert left.as_dict()[layers.DRAINED]["count"] == 3
