"""Share of the window's batches, in percent, whose launch found the
batch's input already on the device, stacked and sent there by the
serving host's event loop when it coalesced the batch, from the engine's
``staged_inputs`` and ``batches`` counters; None for a program without
the counter."""
from chipbench.hostspans import per_batch_ms


def read(ctx):
    # per_batch_ms gives a thousand times staged inputs per batch
    per_batch = per_batch_ms(ctx, ("staged_inputs",))
    return None if per_batch is None else per_batch / 10
