"""`tools/step_compile.py`: what it takes out of a compiled step's text
(names, lines and kernel bodies, which a change of scopes or comments
moves), and its shapes of a cell's state against the state
`Trainer.init_state` really places."""
import os
import sys

import jax

from geomx_tpu.telemetry import layers

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tests", "benchmark"))
from bench_paths import tiny_registry  # noqa: E402
from tools import step_compile  # noqa: E402

TEXT = """HloModule jit_s, is_scheduled=true

FileNames
1 "/root/repo/geomx_tpu/models/decoder.py"

FunctionNames
1 "embed"

FileLocations
1 {file_name_id=1 function_name_id=1 line=431 end_line=431 column=12 end_column=70}

StackFrames
1 {file_location_id=1 parent_frame_id=1}

ENTRY %main (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %fusion.3 = f32[4]{0} fusion(%a), kind=kLoop, calls=%fused, metadata={op_name="jit(s)/step/forward_backward/jvp(Lm)/lm/embed/mul" stack_frame_id=1}
  ROOT %k.5 = f32[4]{0} custom-call(%fusion.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(s)/a{b}/pallas_call"}, backend_config={"custom_call_config":{"body":"TUzvUgFN","needs_layout_passes":true}}
}
"""


def test_stripped_text_keeps_the_program_and_drops_the_names():
    got = list(step_compile.stripped(TEXT.splitlines(keepends=True)))
    text = "".join(got)
    assert "metadata" not in text and "decoder.py" not in text
    assert "TUzvUgFN" not in text and '"body":""' in text
    assert "StackFrames" not in text and "file_location_id" not in text
    assert ("  %fusion.3 = f32[4]{0} fusion(%a), kind=kLoop, calls=%fused\n"
            in got)
    assert 'custom_call_target="tpu_custom_call", backend_config=' in text
    # a scope renamed and a line moved: the same stripped text
    moved = TEXT.replace("lm/embed", "tokens/lookup").replace("431", "433")
    assert list(step_compile.stripped(moved.splitlines(keepends=True))) == got
    # the table still reads the names the stripped text lost
    assert layers.op_layers(TEXT)["fusion.3"].scope.endswith("lm/embed")


def test_abstract_step_has_the_shapes_init_state_places():
    """The tool works a cell's state out by `jax.eval_shape` of the plain
    path's pieces; held to what `init_state` and the loader really give."""
    cell = tiny_registry().cell("tiny-seqcls-bsc")
    trainer, (state, x, y) = step_compile.abstract_step(
        cell, jax.devices()[:cell["chips"]])
    config = cell["config"]
    import numpy as np
    xs, ys = cell["family"].make_data(config, np.random.default_rng(0),
                                      config["per_chip_batch"])
    real = trainer.init_state(jax.random.PRNGKey(0), xs[:2])
    facts = lambda tree: jax.tree.map(
        lambda a: (tuple(a.shape), str(a.dtype), a.sharding), tree)
    assert facts(real) == facts(state)
    loader = trainer.make_loader(xs, ys, config["per_chip_batch"])
    xb, yb = next(iter(loader.epoch(0, prefetch=0)))
    assert facts((xb, yb)) == facts((x, y))
    trainer.train_step.lower(state, x, y)       # and the step takes them
