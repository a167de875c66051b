"""Device time per step of the routed experts held on this chip: every
instruction under scope `moe/experts` (`ops/held_experts`: the sort of the
assignments, and the `while` over the pools with its grouped-product
kernels, forward and backward), all expert layers together.
Source: `_scopes.scope_ms`."""
NAME, UNIT = "moe_experts_ms", "ms"
SCOPE = "moe/experts"


def has_expert_layer(cell):
    kinds = getattr(cell["family"], "layer_kinds", None)
    return kinds is not None and any(
        ffn == "moe" for _, ffn in kinds(cell["config"]))


def applies(cell):
    return has_expert_layer(cell)


def read(ctx):
    from benchmark.layer_metrics import _scopes
    return _scopes.scope_ms(ctx, SCOPE)
