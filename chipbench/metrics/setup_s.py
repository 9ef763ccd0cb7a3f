"""Seconds from process start to the window's start: imports, weights,
the marvel flow, bucket compiles and warm-up."""


def read(ctx):
    return ctx.out["setup_s"]
