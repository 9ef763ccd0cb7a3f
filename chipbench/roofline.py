"""A kernel's share of its roofline, from the trace and the work counts.

The kernel's events that started in the traced window are counted; divided
by the kernel's sites in one model step they give the steps it served.
Its least time is that many times the sum over its sites of max(int8
operations / int8 peak, least bytes / memory bandwidth) at the step's
batch; its time is the summed device duration of those events.  The batch
is the traffic's one bucket: a cell with several buckets cannot tell which
step an event belonged to, and reads nothing.
"""
from __future__ import annotations

from chipbench import work
from chipbench.context import share_percent


def kernel_roofline(ctx, kernel: str):
    buckets = ctx.cell.traffic["serving"]["buckets"]
    trace = ctx.trace
    if trace is None or len(buckets) != 1 or kernel not in trace.kernel_calls:
        return None
    sites = ctx.site_work(buckets[0])
    per_step = sum(1 for s in sites if s["kernel"] == kernel)
    if not per_step:
        return None
    calls, took = trace.kernel_calls[kernel]
    least = calls / per_step * work.least_seconds(sites, kernel, ctx.peaks)
    return share_percent(least, took, f"{kernel}_roofline")
