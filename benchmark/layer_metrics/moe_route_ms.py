"""Device time per step of the expert layers' router: every instruction
under scope `moe/route` (`models/decoder.route`: the float32 score product
at `highest`, the sigmoid, the top-k of score + bias, the weights'
normalisation), forward, rematerialised forward and backward, all expert
layers together.  The top-k is where a wide router shows (22 of 512
against 8 of 128 or 256).  Source: `_scopes.scope_ms`."""
NAME, UNIT = "moe_route_ms", "ms"
SCOPE = "moe/route"


def applies(cell):
    from benchmark.layer_metrics import moe_experts_ms
    return moe_experts_ms.has_expert_layer(cell)


def read(ctx):
    from benchmark.layer_metrics import _scopes
    return _scopes.scope_ms(ctx, SCOPE)
