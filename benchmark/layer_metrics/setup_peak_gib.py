"""GiB of the allocator's high-water mark (`peak_bytes_in_use`) just
before the step's first dispatch: what set-up alone left (the program's
lifecycle record, mark `fit/first_dispatch`).  Where it equals the
cell's `peak_hbm_gib`, that metric is set-up's and not the step's."""
NAME, UNIT = "setup_peak_gib", "GiB"


def applies(cell):
    return True


def read(ctx):
    from benchmark.layer_metrics import _lifecycle
    peak = _lifecycle.mark_bytes(ctx, _lifecycle.FIRST_DISPATCH,
                                 "peak_bytes_in_use")
    return None if peak is None else peak / _lifecycle.GIB
