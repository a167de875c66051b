"""`correct` is a comparison with the plain reference, and a comparison
that fails when it must.  Driven at tiny sizes on the CPU through the
harness's rehearsal entry (no look for a chip, a window of three
segments, counts and `correct` and no timing)."""
import numpy as np
import pytest

from bench_paths import tiny_registry

from benchmark import check, run


def rehearse(name, seed=7):
    return run.run_cell(tiny_registry(), name, seed, 30.0, False,
                        rehearse_segments=3)


def break_the_step(monkeypatch, broken):
    """The harness's trainer gets `broken(real_step)` as its `train_step`:
    the timed path broken underneath, the rest of a run as it is."""
    real_build = run.build_trainer

    def build(cell):
        trainer = real_build(cell)
        trainer.train_step = broken(trainer.train_step)
        return trainer
    monkeypatch.setattr(run, "build_trainer", build)


@pytest.mark.parametrize("name", ["tiny-seqcls-f32", "tiny-resnet-f32",
                                  "tiny-seqcls-bsc", "tiny-seqcls-dense"])
def test_sound_runs_are_correct(name, capsys):
    result = rehearse(name)
    out = capsys.readouterr().out
    assert result["correct"] is True, out
    assert result["segments"] == 3 and result["failed"] == 0
    assert result["attempted"] == 3 * tiny_registry().cell(name)["workload"]["log_every"]
    assert result["metrics"] == {}          # no number under a metric's name
    # every number compared is printed beside its limit
    assert out.count('CHECK {"number"') >= 5
    # the window's host-loop counters, once, though nothing was traced
    assert out.count("LOOP_STATS {") == 1 and '"fit/log_sync"' in out
    assert '"limit": null' not in out
    # and in the result's line, as its last key
    assert list(result)[-1] == "checks"
    assert set(result["checks"]) >= {"loss_gap", "first_grad_error",
                                     "delta_gap", "compiles_in_window"}
    assert all(rec["value"] <= rec["limit"]
               for rec in result["checks"].values())


def test_float32_program_meets_the_reference_to_rounding(capsys):
    rehearse("tiny-seqcls-f32", seed=2 ** 31 + 11)
    lines = [line for line in capsys.readouterr().out.splitlines()
             if line.startswith("CHECK ")]
    import json
    numbers = {rec["number"]: rec["value"]
               for rec in (json.loads(line[6:]) for line in lines)}
    assert numbers["first_grad_gap"] < 1e-5
    assert numbers["first_grad_error"] < 1e-5
    assert numbers["loss_gap"] < 1e-5
    assert numbers["bsc_count_gap"] == 0 and numbers["bsc_held_back"] == 0


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        capsys, monkeypatch):
    def frozen(real):
        return lambda state, xb, yb: (state, real(state, xb, yb)[1])

    # donation would delete the state the frozen step hands back
    import geomx_tpu.train.trainer as trainer_mod
    real_build = trainer_mod.build_train_step
    monkeypatch.setattr(
        trainer_mod, "build_train_step",
        lambda *args, **kwargs: real_build(*args, **{**kwargs, "donate": False}))
    break_the_step(monkeypatch, frozen)
    result = rehearse("tiny-seqcls-f32")
    out = capsys.readouterr().out
    assert result["correct"] is False
    assert '"number": "delta_gap"' in out and '"ok": false' in out


def test_a_part_of_the_batch_left_out_is_not_correct(capsys, monkeypatch):
    def half(real):
        def step(state, xb, yb):
            # the second half of every slot's rows repeats the first
            n = xb.shape[2] // 2
            xb = xb.at[:, :, n:].set(xb[:, :, :n])
            yb = yb.at[:, :, n:].set(yb[:, :, :n])
            return real(state, xb, yb)
        return step

    break_the_step(monkeypatch, half)
    result = rehearse("tiny-seqcls-f32")
    out = capsys.readouterr().out
    assert result["correct"] is False
    assert '{"number": "loss_gap"' in out


def test_traced_segments_end_by_themselves_and_hand_the_state_on(tmp_path):
    """The `--trace 1` path without the chip: four segments of two steps
    over data that holds four, so two epochs, under the profiler; the
    window then goes on from the state they hand back."""
    from benchmark import trace_reduce
    cell = tiny_registry().cell("tiny-seqcls-dense")
    config, traffic = cell["config"], cell["traffic"]
    trainer = run.build_trainer(cell)
    rows = config["per_chip_batch"]
    x, y = cell["family"].make_data(
        config, np.random.default_rng(5),
        rows * (traffic["n_check"] + config["data_steps"]))
    state, _shapes = run.initial_state(cell, trainer, 5, x[:2])
    state = run.traced_segments(cell, trainer, state, x, y, 5, str(tmp_path))
    assert int(np.max(np.asarray(state.step))) == 8
    assert trace_reduce.find_xplane(str(tmp_path)).endswith(".xplane.pb")
    lo = traffic["n_check"] * rows
    stamps, losses, _held = run.run_window(
        trainer, state, trainer.make_loader(x[lo:], y[lo:], rows, seed=5),
        cell["workload"]["log_every"], 30.0, max_segments=2)
    assert len(stamps) == 3 and len(losses) == 2
    cell["workload"]["trace_segments"] = 3       # six steps, data of four
    with pytest.raises(ValueError):
        run.traced_segments(cell, trainer, None, x, y, 5, str(tmp_path))


def test_a_non_finite_loss_counts_as_failed_steps():
    numbers = {"loss_gap": 0.0, "nonfinite_losses": 1.0}
    ok, lines = check.verdict(numbers, {"loss_gap": {"limit": 0.1},
                                        "nonfinite_losses": {"limit": 0}})
    assert not ok and [line["ok"] for line in lines] == [True, False]


def test_a_number_without_a_limit_or_not_finite_is_not_correct():
    ok, lines = check.verdict({"loss_gap": 0.0}, {})
    assert not ok and lines[0]["limit"] is None
    ok, _ = check.verdict({"loss_gap": float("nan")},
                          {"loss_gap": {"limit": 1.0}})
    assert not ok


def test_gradient_readings_and_the_error_of_the_whole_gradient():
    a = [np.array([3.0, 4.0]), np.array([[1.0]])]
    b = [np.array([3.0, 0.0]), np.array([[1.0]])]
    got = check.gradient_readings(a, b)
    np.testing.assert_allclose(got["program"], [5.0, 1.0])
    np.testing.assert_allclose(got["reference"], [3.0, 1.0])
    np.testing.assert_allclose(got["difference"], [4.0, 0.0])
    sides = ({"losses": [1.0], "first_grad": a, "delta_norms": [1.0, 1.0]},
             {"losses": [1.0], "first_grad": b, "delta_norms": [1.0, 1.0]})
    numbers = check.compare(*sides, grad_floor=0.0)
    # the whole difference, norm 4, against the reference's norm sqrt(10);
    # leaf gaps 2 and 0 against floors max(norm, median leaf 2): 2/3, 0
    assert numbers["first_grad_error"] == pytest.approx(4 / 10 ** 0.5)
    assert numbers["first_grad_gap"] == pytest.approx(2.0 / 3.0)
    assert numbers["loss_gap"] == 0 and numbers["delta_gap"] == 0
    # a gradient weaker than the cell's floor is measured against the
    # floor: the error against 8, the gap against a median leaf raised by
    # 8 / sqrt(10)
    numbers = check.compare(*sides, grad_floor=8.0)
    assert numbers["first_grad_error"] == pytest.approx(0.5)
    assert numbers["first_grad_gap"] == pytest.approx(2 / (2 * 8 / 10 ** 0.5))
    assert check.weakness(16.0, 8.0) == 1.0


def test_worst_leaf_gap_measures_against_the_median_leaf():
    reference = np.array([1.0, 2.0, 1e-9, 4.0, 3.0])
    program = reference.copy()
    program[2] = 2e-9            # an all-but-zero leaf may double
    assert check.worst_leaf_gap(program, reference) < 1e-8
    program[1] = 2.2             # a real leaf may not
    assert check.worst_leaf_gap(program, reference) == pytest.approx(0.1)
    # the gap of norms, not the norm of a difference: a leaf that is zero
    # in the program shows fully
    program[3] = 0.0
    assert check.worst_leaf_gap(program, reference) == pytest.approx(1.0)
    with pytest.raises(ValueError):
        check.worst_leaf_gap(program[:3], reference)
