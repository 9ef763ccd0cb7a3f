"""The serving program's own phase spans and counters, as the per-layer
metrics read them.

The program marks each phase of a batch with a ``marvel.serve.*`` span
and adds its seconds to a counter of the engine's ``metrics()`` (see
``repro.runtime.cnn_server``).  A program without them gives None here,
never 0: a trace without the program's spans is not a perfect host.

* :func:`per_batch_ms`: counter seconds over the window per batch, from
  the engine's counters before and after it.
* :func:`idle_shares`: per device, the traced window's idle time (the
  window less the union of the op intervals) intersected with the union
  of each group's spans: ``host`` (the compute thread's own work: stack,
  dispatch, post, handoff) and ``wait`` (the compute thread blocked on
  the result), in percent of the window, averaged over the devices.
"""
from __future__ import annotations

import re

from chipbench import devtrace

PREFIX = "marvel.serve."
GROUPS = {"host": ("stack", "dispatch", "post", "handoff"),
          "wait": ("result_wait",)}
_GROUP_OF = {PREFIX + p: g for g, phases in GROUPS.items() for p in phases}


def per_batch_ms(ctx, keys) -> float | None:
    """Milliseconds per batch resolved in the window, summed over the
    counters ``keys``."""
    before, after = ctx.out["engine_before"], ctx.out["engine_after"]
    if not all(k in before and k in after for k in keys):
        return None
    batches = after["batches"] - before["batches"]
    if batches <= 0:
        return None
    return 1e3 * sum(after[k] - before[k] for k in keys) / batches


def _intersection(a, b) -> float:
    """Length of the intersection of two merged, sorted interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        total += devtrace.overlap(*a[i], *b[j])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_shares(planes) -> dict[str, float] | None:
    """``{group: percent}`` of the traced window in which no op ran on a
    device and a span of the group was open; None where no
    ``marvel.serve.*`` span lies in the window."""
    lo, hi = devtrace._window(planes)
    spans = {g: [] for g in GROUPS}
    found = False
    for pl in planes:
        if pl.name.startswith("/device:"):
            continue
        for events in pl.lines.values():
            for ev in events:
                if not ev.name.startswith(PREFIX):
                    continue
                s, e = devtrace.clip(ev.start, ev.end, lo, hi)
                if e <= s:
                    continue
                found = True
                if ev.name in _GROUP_OF:
                    spans[_GROUP_OF[ev.name]].append((s, e))
    devices = [pl for pl in planes if re.match(r"/device:TPU:\d+$", pl.name)
               and devtrace.OPS_LINE in pl.lines]
    if not found or not devices:
        return None
    merged = {g: devtrace.union(v) for g, v in spans.items()}
    idle_s = dict.fromkeys(GROUPS, 0.0)
    for pl in devices:
        busy = devtrace.union(
            devtrace.clip(ev.start, ev.end, lo, hi)
            for ev in pl.lines[devtrace.OPS_LINE]
            if ev.end > lo and ev.start < hi)
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        idle = [(g0, g1) for g0, g1 in zip(edges[::2], edges[1::2]) if g1 > g0]
        for g in GROUPS:
            idle_s[g] += _intersection(idle, merged[g])
    return {g: 100.0 * s / len(devices) / (hi - lo) for g, s in idle_s.items()}


def idle_share(ctx, group: str) -> float | None:
    """One group's share of the run's trace, None in an untraced run."""
    if ctx.trace_dir is None:
        return None
    shares = idle_shares(devtrace.load(ctx.trace_dir))
    return None if shares is None else shares[group]
