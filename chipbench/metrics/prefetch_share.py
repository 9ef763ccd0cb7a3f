"""Share of the window's batches, in percent, that the compute thread
launched while the batch before them was still in flight (the serving
host's one-batch look-ahead), from the engine's ``prefetched`` and
``batches`` counters; None for a program without the counter."""
from chipbench.hostspans import per_batch_ms


def read(ctx):
    # per_batch_ms gives a thousand times prefetched batches per batch
    per_batch = per_batch_ms(ctx, ("prefetched",))
    return None if per_batch is None else per_batch / 10
