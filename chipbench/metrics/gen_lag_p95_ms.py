"""95th percentile (nearest rank) of how late the load generator sent a
request against its schedule (open traffic only)."""
from chipbench.context import nearest_rank


def read(ctx):
    if ctx.cell.traffic["kind"] != "open":
        return None
    lags = [(r.sent - r.due) * 1e3 for r in ctx.in_window()]
    return nearest_rank(lags, 0.95) if lags else None
