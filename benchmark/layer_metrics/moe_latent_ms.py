"""Device time per step of a LatentMoE's two latent projections: every
instruction under scope `moe/latent`, which `models/decoder.
HeldExpertsLayer` opens around the down-projection into the routed
experts' width and the up-projection out of it (forward, rematerialised
forward and backward, all expert layers together).  Applies where the
configuration has a latent (`moe_latent_size`).
Source: `_scopes.scope_ms`."""
NAME, UNIT = "moe_latent_ms", "ms"
SCOPE = "moe/latent"


def applies(cell):
    from benchmark.layer_metrics import moe_experts_ms
    return (moe_experts_ms.has_expert_layer(cell)
            and bool(cell["config"].get("moe_latent_size")))


def read(ctx):
    from benchmark.layer_metrics import _scopes
    return _scopes.scope_ms(ctx, SCOPE)
