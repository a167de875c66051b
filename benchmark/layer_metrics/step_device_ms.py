"""Device busy time per execution of the step program, busiest chip."""
NAME, UNIT = "step_device_ms", "ms"


def applies(cell):
    return True


def read(ctx):
    t = ctx["trace"]
    if not t or not t["steps"]:
        return None
    return 1e3 * t["busy_s_busiest"] / t["steps"]
