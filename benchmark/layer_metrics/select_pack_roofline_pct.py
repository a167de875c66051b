"""The least time the chip could take for a step's select/pack calls over
the time the `bsc_select_pack` kernels took.

Binding bound: HBM bandwidth.  Counted per bucket of n float32 elements
with k = ceil(ratio n) slots: read g, u, v (12 n bytes), write u, v
(8 n bytes), write k (value, index) pairs (8 k bytes).  One pass over the
data is what the rule needs; the kernel's second pass and its one-hot
matmuls are its own cost and are not counted, so the share cannot pass
100%."""
NAME, UNIT = "select_pack_roofline_pct", "%"


def applies(cell):
    return cell["traffic"]["geoconfig"]["compression"].startswith("bsc")


def bytes_per_step(leaf_sizes, bucket_bytes: int, ratio: float) -> float:
    from benchmark.references import bisparse
    total = 0.0
    for _lo, _hi, n in bisparse.bucket_layout(leaf_sizes, bucket_bytes):
        if n >= bisparse.MIN_SPARSE:
            total += 20.0 * n + 8.0 * bisparse.k_for(n, ratio)
    return total


def read(ctx):
    import jax
    from benchmark.trace_reduce import family_time_s
    t = ctx["trace"]
    if not t or not t["steps"]:
        return None
    kernel_s = family_time_s(t, ("bsc_select_pack",)) / t["steps"]
    if kernel_s <= 0:
        return None
    traffic = ctx["cell"]["traffic"]
    ratio = float(traffic["geoconfig"]["compression"].partition(",")[2])
    sizes = [int(x.size) for x in jax.tree.leaves(ctx["shapes"])]
    least = (bytes_per_step(sizes, traffic["bucket_bytes"], ratio)
             / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / kernel_s
