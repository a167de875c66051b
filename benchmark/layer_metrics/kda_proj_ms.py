"""Device time per step of what surrounds the KDA layers' scan: every
instruction under scope `kda/proj` (the q, k, v, gate and output
products, the short convolutions, the L2 norms, the low-rank gates, the
output norm), forward, rematerialised forward and backward, all KDA
layers together.
Source: `_scopes.scope_ms`."""
NAME, UNIT = "kda_proj_ms", "ms"
SCOPE = "kda/proj"


def applies(cell):
    return hasattr(cell["family"], "kda_scan_shape")


def read(ctx):
    from benchmark.layer_metrics import _scopes
    return _scopes.scope_ms(ctx, SCOPE)
