"""The control: the plain reference put in the program's place and
computed in the nearest precision below the one the configuration states
(float8 for bfloat16).  The comparison has to refuse it, on three seeds,
at a size a test run can hold; sound bfloat16 runs of the program pass
under the same limits."""
import pytest

from bench_paths import tiny_registry

from benchmark import check, seeds
from benchmark.references.numerics import Numerics, next_lower

SEEDS = [2 ** 31 + 101, 7, 990_001]


@pytest.fixture(scope="module")
def readings():
    reg = tiny_registry()
    out = {}
    for name in ("tiny-seqcls-bsc", "tiny-seqcls-dense"):
        out[name] = seeds.read_seeds(reg, name, SEEDS, set(SEEDS),
                                     on_chip=False)
    return out


def test_the_ladder_of_precisions():
    assert next_lower("float32") == "bfloat16"
    assert next_lower("bfloat16") == "float8"
    with pytest.raises(ValueError):
        Numerics("float64")


@pytest.mark.parametrize("name", ["tiny-seqcls-bsc", "tiny-seqcls-dense"])
def test_lower_precision_fails_and_sound_runs_pass(readings, name):
    limits = tiny_registry().cell(name)["workload"]["limits"]
    summary = readings[name]
    grad = summary["first_grad_error"]
    limit = limits["first_grad_error"]["limit"]
    assert grad["sound_max"] < limit < grad["control_min"]
    # room on both sides, as the limit's rule asks
    assert grad["control_min"] > 3 * grad["sound_max"]
    sound = {k: v["sound_max"] for k, v in summary.items()}
    control = {k: v["control_min"] for k, v in summary.items()
               if "control_min" in v}
    sound.update(nonfinite_losses=0.0, compiles_in_window=0.0)
    assert check.verdict(sound, limits)[0] is True
    assert check.verdict(control, limits)[0] is False


def test_float8_rounding_is_coarser_than_bfloat16():
    import jax.numpy as jnp
    import numpy as np
    x = jnp.asarray(np.random.default_rng(0).normal(size=(64, 64)), jnp.float32)
    exact = Numerics("float32").einsum("ij,jk->ik", x, x)
    errors = {}
    for name in ("bfloat16", "float8"):
        got = Numerics(name).einsum("ij,jk->ik", x, x)
        errors[name] = float(jnp.max(jnp.abs(got - exact)))
    assert 0 < errors["bfloat16"] < errors["float8"] / 4
