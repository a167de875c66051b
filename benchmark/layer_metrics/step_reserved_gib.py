"""GiB the allocator reserved between the moment before the step's first
dispatch and the first step's results: `bytes_reserved` at the mark
`fit/first_boundary` less the same at `fit/first_dispatch` (the
program's lifecycle record).  XLA keeps a loaded program's scratch space
reserved, outside `bytes_in_use`, so this is what the step program
holds beside the state."""
NAME, UNIT = "step_reserved_gib", "GiB"


def applies(cell):
    return True


def read(ctx):
    from benchmark.layer_metrics import _lifecycle
    before = _lifecycle.mark_bytes(ctx, _lifecycle.FIRST_DISPATCH,
                                   "bytes_reserved")
    after = _lifecycle.mark_bytes(ctx, _lifecycle.FIRST_BOUNDARY,
                                  "bytes_reserved")
    if before is None or after is None:
        return None
    return (after - before) / _lifecycle.GIB
