"""1 - union of device-op intervals over the traced window, busiest chip.
The traced window is `trace_segments` whole segments of a `Trainer.fit` of
their own, first step's start to last step's end (`trace_reduce`)."""
NAME, UNIT = "device_idle_pct", "%"


def applies(cell):
    return True


def read(ctx):
    t = ctx["trace"]
    if not t:
        return None
    return 100.0 * (1.0 - t["busy_s_busiest"] / t["window_s"])
