"""The least time the chip could take for a step's window-layer attention
(grouped-query heads, a causal band of `sliding_window` keys), forward and
backward, over the time under scope `gqa/window`: the flash kernels and
the layout changes around them.

Binding bound: bf16 matmul peak.  FLOPs from the family's
`window_attention_flops_per_step`: 6 (e_qk + e_v) a seen pair and query
head, W (W + 1) / 2 + (L - W) W pairs a sequence, the band only.  The
backward's recomputation of the scores, the rematerialised forward and the
masked halves of the block pairs that cross the band's edges are the
program's own cost and are not counted, so the share cannot pass 100%."""
NAME, UNIT = "window_attn_roofline_pct", "%"
SCOPE = "gqa/window"


def applies(cell):
    return hasattr(cell["family"], "window_attention_shape")


def read(ctx):
    from benchmark.layer_metrics import _scopes
    ms = _scopes.scope_ms(ctx, SCOPE)
    if not ms:
        return None
    family = ctx["cell"]["family"]
    flops = family.window_attention_flops_per_step(
        family.window_attention_shape(ctx["cell"]["config"]))
    return 100.0 * flops / ctx["peaks"]["bf16_flops_per_s"] / (1e-3 * ms)
