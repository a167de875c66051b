"""Device time per step of the Bi-Sparse boundary probe: every
instruction the program's table puts under a scope that contains
`compress/boundary`, which `BiSparseCompressor.compress` opens around the
boundary's computation, a bucket at a time.  So the number holds however
the probe's samples are fetched (three XLA gathers a bucket before PR 32;
since then the kernel `bsc_boundary_probe` where a bucket's size makes
streaming it cheaper, the gathers above that, nothing at all for a bucket
no larger than the probe), the sort of the samples and the index: the
third part of `compress_engine_ms` beside `select_pack_ms` and
`scatter_add_ms`, and in neither of them nor in `compress_kernels_ms`.
Its floor is one read of g, u, v (12 B an element) and 146 sorts of
8,192 keys in bertlarge-bsc-1c (PERF.md, section 5).  None where the
program has no such scope.  Source: `_scopes.scope_ms`."""
NAME, UNIT = "boundary_ms", "ms"
SCOPE = "compress/boundary"


def applies(cell):
    return cell["traffic"]["geoconfig"]["compression"].startswith("bsc")


def read(ctx):
    from benchmark.layer_metrics import _scopes
    return _scopes.scope_ms(ctx, SCOPE)
