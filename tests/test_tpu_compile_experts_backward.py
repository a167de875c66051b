"""Ask the TPU's compiler, without a TPU (see
``test_tpu_compile_engine.py``): the held experts' walk at the decoder
cells' sizes, backward.  A case is half a minute of libtpu: forward is a file
of its own, so that two workers share them."""

import pytest

import tpu_compile_checks as checks


@pytest.mark.parametrize("direction", ["backward"])
def test_v5e_compiler_accepts_the_ungated_held_experts(chip, direction):
    checks.held_experts_accepted(chip, checks.UNGATED_EXPERTS, direction,
                                 top_k=22, gated=False)


@pytest.mark.parametrize("direction", ["backward"])
@pytest.mark.parametrize("cell", sorted(checks.HELD_EXPERTS))
def test_v5e_compiler_accepts_the_held_experts(chip, cell, direction):
    checks.held_experts_accepted(chip, checks.HELD_EXPERTS[cell], direction)
