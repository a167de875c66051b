"""1 - `samples_per_s_chip` (the window's samples over its time) over the
segment-median rate: the share of the window that stalls of the host loop
took which did not sit in most segments.  Source: the benchmark's own
stamps."""
NAME, UNIT = "host_stall_pct", "%"


def applies(cell):
    return True


def read(ctx):
    return ctx["window"]["host_stall_pct"]
