"""The least time the chip could take for a step's attention, forward and
backward together, over the time the flash-attention kernels took.

Binding bound: bf16 matmul peak.  Counted per layer for B sequences, H
heads, length L, head size e: forward Q K^T and P V (4 B H L^2 e FLOPs);
backward dV, dP, dQ, dK (8 B H L^2 e).  The backward's recomputation of
the scores is the kernel's own cost and is not counted, so the share
cannot pass 100%."""
NAME, UNIT = "flash_attn_roofline_pct", "%"
PREFIXES = ("flash_attention",)


def applies(cell):
    return hasattr(cell["family"], "attention_shape")


def flops_per_step(shape: dict) -> float:
    per_layer = (12.0 * shape["batch"] * shape["heads"]
                 * shape["length"] ** 2 * shape["head_dim"])
    return per_layer * shape["layers"]


def read(ctx):
    from benchmark.trace_reduce import family_time_s
    t = ctx["trace"]
    if not t or not t["steps"]:
        return None
    kernel_s = family_time_s(t, PREFIXES) / t["steps"]
    if kernel_s <= 0:
        return None
    cell = ctx["cell"]
    least = (flops_per_step(cell["family"].attention_shape(cell["config"]))
             / ctx["peaks"]["bf16_flops_per_s"])
    return 100.0 * least / kernel_s
