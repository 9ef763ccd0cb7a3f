"""The preact_matmul kernel's share of its roofline (see chipbench.roofline);
None where the program has no such kernel."""
from chipbench.roofline import kernel_roofline


def read(ctx):
    return kernel_roofline(ctx, "preact_matmul")
