"""chip_smoke.py, rehearsed without the chip.

The script's contract on a machine with no TPU is to FAIL: non-zero exit
and no ``"ok": true`` — there is no fallback to rehearse.  What can be
rehearsed here is everything underneath: each phase function runs at a
tiny size on the CPU (a small stand-in model, kernels in interpret
mode), and the ``--chips 4`` path builds its 2 x 2 mesh on virtual
devices and places every replica-axes leaf on all four.
"""

import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

import chip_smoke
from geomx_tpu.data import load_dataset
from geomx_tpu.models import get_model
from geomx_tpu.telemetry.layers import compile_log

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _run(args, cwd, drop=()):
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, *args], cwd=str(cwd), env=env,
                          capture_output=True, text=True, timeout=240)


@pytest.mark.parametrize("flags", [(), ("--chips", "4")],
                         ids=["one-chip", "four-chips"])
def test_without_a_tpu_the_script_fails_and_prints_no_result(flags):
    out = _run([SCRIPT, *flags], REPO)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr


def test_alone_in_a_directory_the_script_fails(tmp_path):
    """A directory holding chip_smoke.py and nothing else of the repo:
    the program is not there, so neither is a result."""
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    out = _run(["chip_smoke.py"], tmp_path, drop=("PYTHONPATH",))
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "geomx_tpu" in out.stderr


def test_device_phase_refuses_the_cpu():
    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(chip_smoke.SmokeFailure, match="no TPU"):
        chip_smoke.device_phase(1)


@pytest.fixture(scope="module")
def tiny():
    """A stand-in small enough for the CPU: the small CNN, 256 samples."""
    data = load_dataset("synthetic", synthetic_train_n=256, seed=3)
    return {"data": data, "model": get_model("cnn"),
            "counter": compile_log()}


def _last_json(capsys):
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln]
    return json.loads(lines[-1])


def test_kernels_phase_in_interpret_mode(tiny, capsys):
    rec = chip_smoke.kernels_phase(3, interpret=True, model=tiny["model"])
    assert _last_json(capsys) == rec
    assert rec["native"] is False and rec["k"] >= 1
    for name in ("fused_flatten", "fused_unflatten", "bsc_sampled_boundary",
                 "bsc_select_pack",
                 "bsc_scatter_add", "merge_tree",
                 "fused_sgd_momentum/moment", "fused_adam/moments"):
        assert rec["checked"][name] == "bitwise", name
    assert "quantize_2bit" in rec["checked"]


def test_train_phase_steps_all_five_configs(tiny, capsys):
    rec = chip_smoke.train_phase(tiny["data"], 3, tiny["counter"], batch=32,
                                 steps=2, model=tiny["model"])
    assert _last_json(capsys)["phase"] == "train"
    assert tuple(rec["configs"]) == chip_smoke.FIVE
    for name, cfg in rec["configs"].items():
        assert cfg["compiles_after_warmup"] == 0, name
        assert cfg["loss_fixed_batch"][1] < cfg["loss_fixed_batch"][0], name
        assert cfg["timed_steps"] == 2
        # the CPU takes the jnp paths: the fused kernels are a TPU matter
        assert cfg["tpu_custom_call"] is False, name


def test_train_phase_fails_on_a_step_that_does_not_learn(tiny):
    bad = {"losses": [1.0, 1.0], "loss_fixed_batch": [1.0, 1.2],
           "params_moved": 1.0, "compiles_after_warmup": 0}
    with pytest.raises(chip_smoke.SmokeFailure, match="did not fall"):
        chip_smoke._check_steps("x", bad)
    with pytest.raises(chip_smoke.SmokeFailure, match="compilation"):
        chip_smoke._check_steps("x", {**bad, "loss_fixed_batch": [1.0, 0.5],
                                      "compiles_after_warmup": 1})
    with pytest.raises(chip_smoke.SmokeFailure, match="not finite"):
        chip_smoke._check_steps("x", {**bad, "losses": [1.0, float("nan")]})


def test_fit_then_serve_on_the_native_lane(tiny, capsys):
    rec, model, variables = chip_smoke.fit_phase(
        tiny["data"], 3, tiny["counter"], batch=32, steps=4,
        model=tiny["model"])
    assert rec["steps"] == 4 and rec["prefetch"] > 0
    assert 0.0 <= rec["test_acc"] <= 1.0
    served = chip_smoke.serve_phase(model, variables, tiny["data"],
                                    requests=4, model_name="cnn")
    assert _last_json(capsys) == served
    assert served["requests"] == 4 and served["lane"] == "native"
    assert served["donated_input"] is False          # the CPU branch here
    assert served["max_abs_err_vs_model_apply"] <= 1e-4


def test_four_chip_path_builds_the_2x2_mesh_on_virtual_devices(tiny, capsys):
    assert jax.device_count() >= 4
    rec = chip_smoke.multichip_phase(tiny["data"], 3, tiny["counter"],
                                     batch=16, fsa_steps=2, bsc_steps=2,
                                     model=tiny["model"])
    assert _last_json(capsys)["phase"] == "multichip"
    assert rec["fsa"]["gap"] < rec["fsa"]["tolerance"]
    assert "all-reduce" in rec["fsa"]["collectives_2x2"]
    for run, place in rec["placement"].items():
        assert len(place["mesh"]) == 4
        for group in ("params", "opt_state", "model_state", "sync_state",
                      "batch", "step"):
            for devices in place[group]["device_sets"]:
                assert len(devices) == 4, (run, group, devices)
    # bsc carries error-feedback state on the replica axes; fsa has none
    assert rec["placement"]["bsc_2x2"]["sync_state"]["leaves"] > 0
    assert rec["bsc_2x2"]["compiles_after_warmup"] == 0
    assert rec["bsc_2x2"]["collectives"]


def test_a_leaf_on_too_few_devices_fails_the_four_chip_path(tiny):
    trainer = chip_smoke._build_trainer(
        chip_smoke.config_overrides("dist_sync_hips"), 2, 2, tiny["model"])
    x, y = chip_smoke._batches(tiny["data"], 2, 2, 4, 1)[0]
    state = trainer.init_state(jax.random.PRNGKey(0), x[0, 0, :2])
    sharding = trainer.topology.batch_sharding(trainer.mesh)
    xb, yb = jax.device_put(x, sharding), jax.device_put(y, sharding)
    chip_smoke._placement(trainer, state, xb, yb, 4)     # all on four
    stray = jax.device_put(x, jax.devices()[0])           # one device
    with pytest.raises(chip_smoke.SmokeFailure, match="lives on 1 device"):
        chip_smoke._placement(trainer, state, stray, yb, 4)
