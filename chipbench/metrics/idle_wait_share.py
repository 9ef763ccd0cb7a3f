"""Share of the traced window in which no op ran on the device and the
compute thread was waiting on a result (a ``marvel.serve.result_wait``
span), in percent."""
from chipbench.hostspans import idle_share


def read(ctx):
    return idle_share(ctx, "wait")
