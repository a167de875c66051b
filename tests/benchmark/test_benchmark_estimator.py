"""The window's rate is all its samples over all its time: a stall of
the host, one-off or periodic, stays in it.  The segment median beside it
drops a one-off stall and keeps a periodic one."""
import pytest

import bench_paths  # noqa: F401

from benchmark import estimator


def stamps_from(durations, start=10.0):
    out = [start]
    for d in durations:
        out.append(out[-1] + d)
    return out


def test_the_rate_holds_one_slow_segment_and_the_median_drops_it():
    steady = estimator.window_summary(stamps_from([2.0] * 15), 32, 1)
    hiccup = estimator.window_summary(stamps_from([2.0] * 7 + [3.5] + [2.0] * 7),
                                      32, 1)
    assert steady["mean_per_chip"] == pytest.approx(16.0)
    assert hiccup["mean_per_chip"] == pytest.approx(15 * 32 / 31.5)
    assert hiccup["median_per_chip"] == pytest.approx(steady["median_per_chip"])
    assert steady["host_stall_pct"] == pytest.approx(0.0, abs=1e-9)
    assert hiccup["host_stall_pct"] == pytest.approx(100 * (1 - 30 / 31.5))


def test_both_keep_a_periodic_stall():
    steady = estimator.window_summary(stamps_from([2.0] * 15), 32, 1)
    periodic = estimator.window_summary(stamps_from([2.2] * 15), 32, 1)
    for key in ("mean_per_chip", "median_per_chip"):
        assert periodic[key] == pytest.approx(steady[key] * 2.0 / 2.2)
    assert periodic["host_stall_pct"] == pytest.approx(0.0, abs=1e-9)


def test_rates_are_per_chip_and_per_segment():
    s = estimator.window_summary(stamps_from([1.0, 2.0, 4.0]), 64, 4)
    assert s["rates_per_chip"] == pytest.approx([16.0, 8.0, 4.0])
    assert s["segments"] == 3 and s["window_s"] == pytest.approx(7.0)
    assert s["median_per_chip"] == pytest.approx(8.0)
    assert s["mean_per_chip"] == pytest.approx(3 * 64 / 7.0 / 4)


def test_a_window_without_a_whole_segment_is_an_error():
    with pytest.raises(ValueError):
        estimator.window_summary([5.0], 64, 1)
