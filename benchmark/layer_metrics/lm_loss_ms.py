"""Device time per step of the decoder's blocked next-token loss: every
instruction under scope `lm/loss` (the head's product a block of tokens at
a time, logsumexp, and their backward).
Source: `_scopes.scope_ms`."""
NAME, UNIT = "lm_loss_ms", "ms"
SCOPE = "lm/loss"


def applies(cell):
    return hasattr(cell["family"], "layer_kinds")


def read(ctx):
    from benchmark.layer_metrics import _scopes
    return _scopes.scope_ms(ctx, SCOPE)
