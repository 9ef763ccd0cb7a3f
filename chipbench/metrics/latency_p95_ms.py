"""95th percentile (nearest rank) of the latency of every request due in
the window, from its scheduled send time to the client having its answer;
a request that failed or was never answered counts as infinitely late."""
import math

from chipbench.context import nearest_rank


def read(ctx):
    lat = [(r.done - r.due) * 1e3 if r.error is None and r.done is not None
           else math.inf for r in ctx.in_window()]
    p95 = nearest_rank(lat, 0.95)
    return p95 if math.isfinite(p95) else None
