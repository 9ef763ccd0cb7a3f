"""Share of the traced window in which no op ran on the device and the
compute thread was in its own work (a ``marvel.serve.stack``,
``dispatch``, ``post`` or ``handoff`` span), in percent."""
from chipbench.hostspans import idle_share


def read(ctx):
    return idle_share(ctx, "host")
