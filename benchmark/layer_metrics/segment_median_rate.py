"""Median over the window's segments of samples per segment over segment
time, per chip: the loop's steady rate.  A one-off stall of the host sits
in one segment and does not move it; `samples_per_s_chip`, the window's
samples over the window's time, holds the stall.  Source: the benchmark's
own stamps."""
NAME, UNIT = "segment_median_rate", "samples/s/chip"


def applies(cell):
    return True


def read(ctx):
    return ctx["window"]["median_per_chip"]
