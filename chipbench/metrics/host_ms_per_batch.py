"""Compute-thread host milliseconds per batch over the window: stacking
and padding, dispatch (argument transfer, enqueue) and post-processing,
from the engine's ``stack_s``, ``dispatch_s`` and ``post_s`` counters."""
from chipbench.hostspans import per_batch_ms


def read(ctx):
    return per_batch_ms(ctx, ("stack_s", "dispatch_s", "post_s"))
