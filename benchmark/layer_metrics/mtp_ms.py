"""Device time per step of the multi-token-prediction modules: every
instruction under scope `mtp/module` (`models/decoder.DecoderLM`: each
depth's two norms, the lookup of the next token's embedding and the
joining matrix under `mtp/combine`, its block's latent attention and
expert layer, its output norm and its blocked pass over the shared head),
forward, rematerialised forward and backward.  The same instructions also
count under their own scopes (`mla/*`, `moe/*`, `attn/core`, `lm/loss`
nest inside), so this is a part of those numbers, not beside them.
None on a program that has no such scope.
Source: `_scopes.scope_ms`."""
NAME, UNIT = "mtp_ms", "ms"
SCOPE = "mtp/module"


def applies(cell):
    return cell["config"].get("num_nextn_predict_layers", 0) > 0


def read(ctx):
    from benchmark.layer_metrics import _scopes
    return _scopes.scope_ms(ctx, SCOPE)
