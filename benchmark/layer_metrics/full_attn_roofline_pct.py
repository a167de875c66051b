"""The least time the chip could take for a step's full-attention cores
(causal over every earlier key) of a model whose every attention layer is
a full one, forward and backward, over the time under scope `gqa/global`:
the flash kernels and the layout changes around them.
`global_attn_roofline_pct` is the same share in the families with window
layers (its `applies` keys on `window_attention_shape`).

Binding bound: bf16 matmul peak.  FLOPs from the family's
`global_attention_flops_per_step`: 6 (e_qk + e_v) a seen pair and query
head, L (L + 1) / 2 pairs a sequence, every application of a layer
counted (`global_attention_shape`'s `layers`).  The backward's
recomputation of the scores and the rematerialised forward are the
program's own cost and are not counted, so the share cannot pass 100%."""
NAME, UNIT = "full_attn_roofline_pct", "%"
SCOPE = "gqa/global"


def applies(cell):
    from benchmark.layer_metrics import attn_proj_ms
    return attn_proj_ms.applies(cell)


def read(ctx):
    from benchmark.layer_metrics import _scopes
    ms = _scopes.scope_ms(ctx, SCOPE)
    if not ms:
        return None
    family = ctx["cell"]["family"]
    flops = family.global_attention_flops_per_step(
        family.global_attention_shape(ctx["cell"]["config"]))
    return 100.0 * flops / ctx["peaks"]["bf16_flops_per_s"] / (1e-3 * ms)
