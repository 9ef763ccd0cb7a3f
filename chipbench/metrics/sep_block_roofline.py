"""The sep_block kernel's share of its roofline (see chipbench.roofline)."""
from chipbench.roofline import kernel_roofline


def read(ctx):
    return kernel_roofline(ctx, "sep_block")
