"""The least time the chip could take for a step's state-space
recurrences, forward and backward, over the time under scope `ssd/scan`.

Both bounds are computed from shapes only, so they are the same whatever
implements the scan (the family's `ssd_scan_flops_per_step`, 12 P N a
token and held head, over the bf16 peak; `ssd_scan_bytes_per_step`, X, dt,
B, C, Y and their cotangents once each way, over the HBM peak) and the
larger is the least time.  **Binding bound: HBM bytes** at P = 64, N = 128
with 16 heads on one B/C group (11,968 B against 1.57 MFLOP a token:
14.6 ns against 8.0 ns on a v5e).  A chunked form's extra products, its
decay matrices and the rematerialised forward are the program's own cost
and are not counted, so the share cannot pass 100%."""
NAME, UNIT = "ssd_scan_roofline_pct", "%"


def applies(cell):
    return hasattr(cell["family"], "ssd_scan_shape")


def read(ctx):
    from benchmark.layer_metrics import _scopes, ssd_scan_ms
    ms = _scopes.scope_ms(ctx, ssd_scan_ms.SCOPE)
    if not ms:
        return None
    family, peaks = ctx["cell"]["family"], ctx["peaks"]
    shape = family.ssd_scan_shape(ctx["cell"]["config"])
    least_s = max(
        family.ssd_scan_flops_per_step(shape) / peaks["bf16_flops_per_s"],
        family.ssd_scan_bytes_per_step(shape) / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (1e-3 * ms)
