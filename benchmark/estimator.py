"""Throughput from the segments of one window.

A segment is `log_every` steps of `Trainer.fit`, closed on the device by
the `device_get` at fit's log boundary and stamped by the benchmark's
`log_fn`.  The window is the whole segments that end inside `--seconds`.
The rate is all the samples of the window over all its time, first stamp
to last: a stall of the host loop, periodic or one-off, is time a user
pays for and stays in the number.  Beside it stands the median over
segments of samples per segment over segment time, which a one-off stall
in one segment does not move: the two together say whether a slow run
was slow throughout or stalled once (`host_stall_pct`)."""
from __future__ import annotations

import statistics


def segment_rates(stamps, samples_per_segment: float):
    """`stamps`: window start, then the end of each whole segment."""
    return [samples_per_segment / (b - a)
            for a, b in zip(stamps, stamps[1:])]


def window_summary(stamps, samples_per_segment: float, chips: int) -> dict:
    rates = segment_rates(stamps, samples_per_segment)
    if not rates:
        raise ValueError("no whole segment ended inside the window")
    median = statistics.median(rates)
    mean = samples_per_segment * len(rates) / (stamps[-1] - stamps[0])
    return {
        "segments": len(rates),
        "rates_per_chip": [r / chips for r in rates],
        "mean_per_chip": mean / chips,
        "median_per_chip": median / chips,
        "host_stall_pct": 100.0 * (1.0 - mean / median),
        "window_s": stamps[-1] - stamps[0],
    }
