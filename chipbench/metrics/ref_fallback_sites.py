"""Dispatch sites of the served program that fell back from their kernel
to the jnp oracle while its bucket executables were traced, from the
program's ``ref_fallbacks`` counter (read before the window); None for a
program without the counter."""


def read(ctx):
    return ctx.out["engine_before"].get("ref_fallbacks")
