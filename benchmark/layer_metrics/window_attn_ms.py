"""Device time per step of the window layers' attention: every
instruction under scope `gqa/window` (`models/afmoe.GQAMixer` opens it
around `fused_attention`: the grouped-query flash kernels over the band's
block pairs and the pads, reshapes and `delta` around them), forward,
rematerialised forward and backward, all window layers together.
Source: `_scopes.scope_ms`."""
NAME, UNIT = "window_attn_ms", "ms"
SCOPE = "gqa/window"


def applies(cell):
    return hasattr(cell["family"], "window_attention_shape")


def read(ctx):
    from benchmark.layer_metrics import _scopes
    return _scopes.scope_ms(ctx, SCOPE)
