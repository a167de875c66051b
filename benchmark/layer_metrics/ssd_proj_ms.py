"""Device time per step of what surrounds the state-space scan in a
Mamba-2 layer: every instruction under scope `ssd/proj` (the in-projection
to z, x, B, C and dt, the short convolution with its bias and SiLU,
softplus, the skip D x, the gate, the gated group norm and the
out-projection), forward, rematerialised forward and backward, all layers
together.  Source: `_scopes.scope_ms`."""
NAME, UNIT = "ssd_proj_ms", "ms"
SCOPE = "ssd/proj"


def applies(cell):
    return hasattr(cell["family"], "ssd_scan_shape")


def read(ctx):
    from benchmark.layer_metrics import _scopes
    return _scopes.scope_ms(ctx, SCOPE)
