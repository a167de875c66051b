"""The least time the chip could take for a step's KDA recurrences,
forward and backward, over the time under scope `kda/scan`.

Both bounds are computed (the family's `kda_scan_flops_per_step`, 21 d_k
d_v a token and head, over the bf16 peak; `kda_scan_bytes_per_step`, the
operands once each way, over the HBM peak) and the larger is the least
time.  **Binding bound: HBM bytes** at d_k = d_v = 128 (4,352 B against
344 kFLOP a token and head: 5.3 ns against 1.7 ns on a v5e).  The chunked
form's extra products and the rematerialised forward are the program's own
cost and are not counted, so the share cannot pass 100%."""
NAME, UNIT = "kda_scan_roofline_pct", "%"


def applies(cell):
    return hasattr(cell["family"], "kda_scan_shape")


def read(ctx):
    from benchmark.layer_metrics import _scopes, kda_scan_ms
    ms = _scopes.scope_ms(ctx, kda_scan_ms.SCOPE)
    if not ms:
        return None
    family, peaks = ctx["cell"]["family"], ctx["peaks"]
    shape = family.kda_scan_shape(ctx["cell"]["config"])
    least_s = max(
        family.kda_scan_flops_per_step(shape) / peaks["bf16_flops_per_s"],
        family.kda_scan_bytes_per_step(shape) / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (1e-3 * ms)
