"""The least time the chip could take for a step's global-layer attention
(grouped-query heads, causal over every earlier key), forward and
backward, over the time under scope `gqa/global`: the flash kernels and
the layout changes around them.

Binding bound: bf16 matmul peak.  FLOPs from the family's
`global_attention_flops_per_step`: 6 (e_qk + e_v) a seen pair and query
head, L (L + 1) / 2 pairs a sequence.  The backward's recomputation of the
scores and the rematerialised forward are the program's own cost and are
not counted, so the share cannot pass 100%."""
NAME, UNIT = "global_attn_roofline_pct", "%"
SCOPE = "gqa/global"


def applies(cell):
    return hasattr(cell["family"], "window_attention_shape")


def read(ctx):
    from benchmark.layer_metrics import _scopes
    ms = _scopes.scope_ms(ctx, SCOPE)
    if not ms:
        return None
    family = ctx["cell"]["family"]
    flops = family.global_attention_flops_per_step(
        family.global_attention_shape(ctx["cell"]["config"]))
    return 100.0 * flops / ctx["peaks"]["bf16_flops_per_s"] / (1e-3 * ms)
