"""Images per batch the engine resolved during the window, from the
engine's own counters (``completed`` and ``batches``)."""


def read(ctx):
    before, after = ctx.out["engine_before"], ctx.out["engine_after"]
    batches = after["batches"] - before["batches"]
    if batches <= 0:
        return None
    return (after["completed"] - before["completed"]) / batches
