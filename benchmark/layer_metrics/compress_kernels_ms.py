"""Device time per step of the compression engine's named kernels:
select/pack, scatter-add, bucket flatten/unflatten, merge.  The engine's
XLA ops (boundary probe, copies, converts) carry no such name and are not
in this number (PERF.md, open questions: named scopes)."""
NAME, UNIT = "compress_kernels_ms", "ms"
PREFIXES = ("bsc_select_pack", "bsc_scatter_add", "fused_flatten",
            "fused_unflatten", "merge_sorted_pairs", "_merge_tree_pallas")


def applies(cell):
    return cell["traffic"]["geoconfig"]["compression"].startswith("bsc")


def read(ctx):
    from benchmark.trace_reduce import family_time_s
    t = ctx["trace"]
    if not t or not t["steps"]:
        return None
    return 1e3 * family_time_s(t, PREFIXES) / t["steps"]
