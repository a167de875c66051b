"""Device time per step of the held experts' row moves: every instruction
under scope `moe/dispatch`, which `ops/held_experts` opens inside
`moe/experts` around a pool's gathers (x forward; x and dy backward) and
scatter-adds (y forward, dx backward), whichever implementation
`ops/dispatch.py` picks for the latter: the kernel `moe_row_scatter_add`
with the fill and the layout pass XLA puts at its doors (a row width of
whole tiles: the Trinity cell's 2,048), or XLA's own scatter-add (the Kimi
cell's 2,304); the gathers are XLA's.  A part of `moe_experts_ms`; what is
left of that is the sort, the grouped products and the elementwise passes
over the pool.  None where the program has no such scope (the parent of
the PR that added it).  Source: `_scopes.scope_ms`."""
NAME, UNIT = "moe_dispatch_ms", "ms"
SCOPE = "moe/dispatch"


def applies(cell):
    from benchmark.layer_metrics import moe_experts_ms
    return moe_experts_ms.has_expert_layer(cell)


def read(ctx):
    from benchmark.layer_metrics import _scopes
    return _scopes.scope_ms(ctx, SCOPE)
