"""Device time per step of what surrounds the grouped-query attention
cores: every instruction under scope `gqa/proj` (the q, k, v and gate
products, the per-head q/k norms, rotary, the gating of the output and the
output product), forward, rematerialised forward and backward, all layers
together.
Source: `_scopes.scope_ms`."""
NAME, UNIT = "gqa_proj_ms", "ms"
SCOPE = "gqa/proj"


def applies(cell):
    return hasattr(cell["family"], "window_attention_shape")


def read(ctx):
    from benchmark.layer_metrics import _scopes
    return _scopes.scope_ms(ctx, SCOPE)
