"""The whole model step's share of the chip's int8 peak: images answered
in the window times the reference's conv and dot operations per image,
over the window's length times the peak of the chips used."""
from chipbench.context import share_percent


def read(ctx):
    ops = len(ctx.answered_in_window()) * ctx.out["flops_per_image"]
    least = ops / (ctx.peaks["int8_ops_per_s"] * ctx.chips)
    return share_percent(least, ctx.seconds, "mfu")
