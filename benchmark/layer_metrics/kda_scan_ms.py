"""Device time per step of the KDA layers' chunked delta-rule scan: every
instruction under scope `kda/scan` (`ops/kda.kda_chunked`: the chunks'
score matrices, the triangular inverse, the `while` that hands the state
from chunk to chunk), forward, rematerialised forward and backward, all
KDA layers together.
Source: `_scopes.scope_ms`."""
NAME, UNIT = "kda_scan_ms", "ms"
SCOPE = "kda/scan"


def applies(cell):
    return hasattr(cell["family"], "kda_scan_shape")


def read(ctx):
    from benchmark.layer_metrics import _scopes
    return _scopes.scope_ms(ctx, SCOPE)
