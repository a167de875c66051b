"""Device time per step of the dense SwiGLU halves: every instruction
under scope `ffn/mlp` (`models/decoder.FFNBranch` around its `MLP`: the
gate, up and down products and the activation between them), forward,
rematerialised forward and backward, all dense layers and, in a looped
stack, all their applications together, in a model whose feed-forward
halves are all dense.  None on a program that has no
such scope (the parent of PR 48).
Source: `_scopes.scope_ms`."""
NAME, UNIT = "dense_mlp_ms", "ms"
SCOPE = "ffn/mlp"


def applies(cell):
    """Where every feed-forward half is a dense MLP.  The scope is also in
    the steps of the cells with a dense lead (Kimi's, Trinity's, GLM's:
    one layer of five or six), which stay unlisted here:
    `tests/benchmark/test_benchmark_glm4_moe_lite.py` holds that cell's
    applying readers to a list, and PR 48 could edit no accepted file."""
    kinds = getattr(cell["family"], "layer_kinds", None)
    return kinds is not None and all(
        ffn == "mlp" for _, ffn in kinds(cell["config"]))


def read(ctx):
    from benchmark.layer_metrics import _scopes
    return _scopes.scope_ms(ctx, SCOPE)
