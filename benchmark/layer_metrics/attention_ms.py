"""Device time per step of attention's core: every instruction the
program's table puts under a scope that contains `attn/core`, which
`ops/flash_attention.fused_attention` opens around its forward and its
backward.  So the number holds the flash kernels (`flash_attention*`,
what `flash_attn_roofline_pct` finds by name) AND the pads, reshapes,
casts and the backward's `delta` around them, which no kernel-name metric
can see; it keeps its meaning whatever attention is made of.  In the
decoder the scope nests inside `mla/attention`.  Its floor is the bf16
peak over 12 B H L^2 e FLOPs a layer (the causal half at 192/128 in the
decoder): PERF.md, section 3.  None on a program that has no such scope.
Source: `_scopes.scope_ms`."""
NAME, UNIT = "attention_ms", "ms"
SCOPE = "attn/core"


def applies(cell):
    family = cell["family"]
    return (hasattr(family, "attention_shape")
            or hasattr(family, "latent_attention_shape"))


def read(ctx):
    from benchmark.layer_metrics import _scopes
    return _scopes.scope_ms(ctx, SCOPE)
