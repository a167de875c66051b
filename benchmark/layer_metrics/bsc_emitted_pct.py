"""Pairs emitted over k, summed over the buckets of the first push, read
from the state that step left behind (outside the window)."""
NAME, UNIT = "bsc_emitted_pct", "%"


def applies(cell):
    return cell["traffic"]["geoconfig"]["compression"].startswith("bsc")


def read(ctx):
    facts = ctx["program"].get("bsc")
    if not facts or not facts["k"]:
        return None
    return 100.0 * facts["count"] / facts["k"]
