"""Milliseconds per batch the compute thread waited on the result (the
device run and the device-to-host copy) over the window, from the
engine's ``result_wait_s`` counter."""
from chipbench.hostspans import per_batch_ms


def read(ctx):
    return per_batch_ms(ctx, ("result_wait_s",))
