"""Device time per step of the Mamba-2 layers' state-space scan: every
instruction under scope `ssd/scan` (`ops/ssd.ssd_chunked`: a chunk's
masked decay matrix and score product, the products that read and write
the state, the chunk-to-chunk hand-over), forward, rematerialised forward
and backward, all Mamba-2 layers together, whatever implements the scan.
None where the program has no such scope (the parent of the PR that added
it).  Source: `_scopes.scope_ms`."""
NAME, UNIT = "ssd_scan_ms", "ms"
SCOPE = "ssd/scan"


def applies(cell):
    return hasattr(cell["family"], "ssd_scan_shape")


def read(ctx):
    from benchmark.layer_metrics import _scopes
    return _scopes.scope_ms(ctx, SCOPE)
