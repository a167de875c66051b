"""The least time the chip could take for a step's latent attention
(causal, 192-wide q and k, 128-wide v), forward and backward, over the
time under scope `mla/attention`: the flash kernels and the layout changes
around them.

Binding bound: bf16 matmul peak.  FLOPs from the family's
`latent_attention_flops_per_step`: (3 e_qk + 3 e_v) B H L^2 a layer, the
causal half only.  The backward's recomputation of the scores and the
rematerialised forward are the program's own cost and are not counted, so
the share cannot pass 100%."""
NAME, UNIT = "latent_attn_roofline_pct", "%"
SCOPE = "mla/attention"


def applies(cell):
    return hasattr(cell["family"], "latent_attention_shape")


def read(ctx):
    from benchmark.layer_metrics import _scopes
    ms = _scopes.scope_ms(ctx, SCOPE)
    if not ms:
        return None
    family = ctx["cell"]["family"]
    flops = family.latent_attention_flops_per_step(
        family.latent_attention_shape(ctx["cell"]["config"]))
    return 100.0 * flops / ctx["peaks"]["bf16_flops_per_s"] / (1e-3 * ms)
