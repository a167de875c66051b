"""Device time per step of what surrounds the attention cores of a model
whose every attention layer is a full one: every instruction under scope
`gqa/proj` (the q, k and v products, rotary, the output product),
forward, rematerialised forward and backward, all applications together.
`gqa_proj_ms` is the same scope's time in the families with window layers
(its `applies` keys on `window_attention_shape`).
Source: `_scopes.scope_ms`."""
NAME, UNIT = "attn_proj_ms", "ms"
SCOPE = "gqa/proj"


def applies(cell):
    """A family with a global attention shape and no window one, whose
    every layer's mixer is full attention (Nemotron-H's family has such a
    shape for its one attention layer among Mamba-2 and expert layers: its
    cell's attention is a twentieth of its step and stays unlisted)."""
    family = cell["family"]
    return (hasattr(family, "global_attention_shape")
            and not hasattr(family, "window_attention_shape")
            and all(mixer == "global"
                    for mixer, _ in family.layer_kinds(cell["config"])))


def read(ctx):
    from benchmark.layer_metrics import _scopes
    return _scopes.scope_ms(ctx, SCOPE)
