"""Device time per step of the Bi-Sparse select/pack: every instruction
the program's table puts under a scope that contains `bsc/select_pack`,
which `BiSparseCompressor.compress` opens around its whole fused call.
So the number holds the kernels (`bsc_select_pack*`, what
`select_pack_roofline_pct` and `compress_kernels_ms` find by name) AND
the XLA ops of the placement's schedule between them (the prefix sums of
the per-tile counts, the visit lists), which no kernel-name metric can
see; it keeps its meaning whatever the select/pack is made of.  Its floor
is one read of g, u, v and one write of u, v (PERF.md, section 5).
Source: `_scopes.scope_ms`."""
NAME, UNIT = "select_pack_ms", "ms"
SCOPE = "bsc/select_pack"


def applies(cell):
    return cell["traffic"]["geoconfig"]["compression"].startswith("bsc")


def read(ctx):
    from benchmark.layer_metrics import _scopes
    return _scopes.scope_ms(ctx, SCOPE)
