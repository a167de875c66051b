"""Device time per step of what surrounds the latent-attention cores:
every instruction under scope `mla/proj` (`models/decoder.LatentMixer`:
the query's product or its low-rank pair with their norm, the keys' and
values' down- and up-projection with their norm, rotary on the rope parts
where the configuration names positions, building q and k from their
parts, and the product out), forward, rematerialised forward and backward,
all latent layers together (a multi-token-prediction module's block among
them).
Source: `_scopes.scope_ms`."""
NAME, UNIT = "mla_proj_ms", "ms"
SCOPE = "mla/proj"


def applies(cell):
    return hasattr(cell["family"], "latent_attention_shape")


def read(ctx):
    from benchmark.layer_metrics import _scopes
    return _scopes.scope_ms(ctx, SCOPE)
