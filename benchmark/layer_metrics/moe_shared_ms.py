"""Device time per step of the expert layers' shared expert: every
instruction under scope `moe/shared` (`models/decoder.HeldExpertsLayer`:
the dense MLP every token passes, SwiGLU or un-gated squared ReLU),
forward, rematerialised forward and backward, all expert layers together.
Source: `_scopes.scope_ms`."""
NAME, UNIT = "moe_shared_ms", "ms"
SCOPE = "moe/shared"


def applies(cell):
    from benchmark.layer_metrics import moe_experts_ms
    return moe_experts_ms.has_expert_layer(cell)


def read(ctx):
    from benchmark.layer_metrics import _scopes
    return _scopes.scope_ms(ctx, SCOPE)
