"""Seconds the program spent lowering and compiling its bucket
executables during set-up, from its ``build_s`` counter."""


def read(ctx):
    return ctx.out["engine_before"].get("build_s")
