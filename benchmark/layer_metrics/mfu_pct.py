"""Model FLOPs per sample (forward + backward from shapes, the family's
`train_flops_per_sample`, no recomputation, Pallas kernels included
because nothing is read from the compiler) x samples/s/chip over the
table's bf16 peak."""
NAME, UNIT = "mfu_pct", "%"


def applies(cell):
    return True


def read(ctx):
    cell = ctx["cell"]
    flops = cell["family"].train_flops_per_sample(cell["config"])
    return (100.0 * flops * ctx["window"]["mean_per_chip"]
            / ctx["peaks"]["bf16_flops_per_s"])
