"""Device time per step of a looped stack's exit gate: every instruction
under scope `loop/exit` (`models/decoder.DecoderLM.looped_loss`: the
gate's product on the T normed streams, the exit distribution, the
entropy, and their backward).  The T head passes stand under `lm/loss`.
None on a program that has no such scope.
Source: `_scopes.scope_ms`."""
NAME, UNIT = "loop_exit_ms", "ms"
SCOPE = "loop/exit"


def applies(cell):
    return cell["config"].get("total_ut_steps", 1) > 1


def read(ctx):
    from benchmark.layer_metrics import _scopes
    return _scopes.scope_ms(ctx, SCOPE)
