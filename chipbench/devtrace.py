"""From a profiler trace to the numbers the per-layer metrics read.

The traced window is the span :data:`WINDOW_SPAN` on the
host.  Within it, per device (``/device:TPU:<n>`` planes):

* busy time: the union of the intervals of the ``XLA Ops`` events;
* per-kernel time: the summed durations of the op events of each name.
  An op event is named by its HLO instruction (``%fused_conv.25 = ...``);
  a Pallas kernel's instruction carries its ``pallas_call`` name, so the
  instruction name without its numeric suffix is the kernel's name, and
  other ops get theirs the same way (``pad``, ``copy``, ``..._fusion``);
* step executions: the ``XLA Modules`` events;
* idle gaps: the window less the busy union, each labelled with the span
  of this benchmark (``chipbench.*``: generator submit, result receipt)
  and the runtime's host event (``PjitFunction``, ``Transpose::Execute``,
  ...) that overlap it most, as ``"<span> | <event>"``; ``-`` where none
  does.

Times are clipped to the window.  Values are seconds, averaged over the
devices where the metric says so.
"""
from __future__ import annotations

import collections
import pathlib
import re
from dataclasses import dataclass, field

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "chipbench.traced_window"
BENCH_PREFIX = "chipbench."
_OP = re.compile(r"%?([\w-]+)[\w.-]*\s*=")


@dataclass
class Event:
    name: str
    start: float  # seconds on the trace's clock
    end: float
    stats: dict = field(default_factory=dict)


@dataclass
class Plane:
    name: str
    lines: dict  # line name -> [Event]


def load(trace_dir) -> list[Plane]:
    """The newest ``.xplane.pb`` under ``trace_dir``, as planes."""
    import jax

    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(str(files[-1]))
    planes = []
    for pl in data.planes:
        lines = collections.defaultdict(list)
        for ln in pl.lines:
            for e in ln.events:
                start = e.start_ns * 1e-9
                lines[ln.name].append(Event(
                    e.name, start, start + e.duration_ns * 1e-9,
                    {k: v for k, v in e.stats}))
        planes.append(Plane(pl.name, dict(lines)))
    return planes


def kernel_of(ev: Event) -> str:
    """The op's HLO instruction name up to its first dot (``.25``,
    ``.83.clone``): for a Pallas kernel, its ``pallas_call`` name."""
    m = _OP.match(ev.name)
    return m.group(1) if m else ev.name


def union(intervals):
    """Merged, sorted, non-overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def overlap(a0, a1, b0, b1) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


@dataclass
class Reduced:
    window: tuple[float, float]
    devices: int
    busy_s: float  # mean over devices
    kernel_s: dict  # kernel -> seconds in the window, summed over devices
    # kernel -> (events that started in the window, their whole seconds)
    kernel_calls: dict
    executions: int  # step executions started in the window, all devices
    gaps: list  # (seconds, label), longest first, all devices

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def breakdown(self) -> dict:
        ops = sorted(self.kernel_s.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[label, s] for s, label in self.gaps[:10]]}


def _window(planes) -> tuple[float, float]:
    for pl in planes:
        for events in pl.lines.values():
            for ev in events:
                if ev.name == WINDOW_SPAN:
                    return ev.start, ev.end
    raise ValueError(f"no {WINDOW_SPAN} span in the trace")


def _host_spans(planes):
    bench, other = [], []
    for pl in planes:
        if pl.name.startswith("/device:"):
            continue
        for events in pl.lines.values():
            for ev in events:
                if ev.name == WINDOW_SPAN or ev.end <= ev.start:
                    continue
                (bench if ev.name.startswith(BENCH_PREFIX) else other
                 ).append(ev)
    return bench, other


def _most(g0, g1, spans) -> str:
    best, name = 0.0, "-"
    for ev in spans:
        ov = overlap(g0, g1, ev.start, ev.end)
        if ov > best:
            best, name = ov, ev.name
    return name


def _label(g0, g1, bench, other) -> str:
    return f"{_most(g0, g1, bench)} | {_most(g0, g1, other)}"


def reduce(planes) -> Reduced:
    lo, hi = _window(planes)
    devices = [pl for pl in planes
               if re.match(r"/device:TPU:\d+$", pl.name) and OPS_LINE in pl.lines]
    if not devices:
        raise ValueError("no TPU device plane with op events in the trace")
    bench, other = _host_spans(planes)
    busy_total, kernel_s, executions, gaps = 0.0, collections.Counter(), 0, []
    calls, call_s = collections.Counter(), collections.Counter()
    for pl in devices:
        spans = []
        for ev in pl.lines[OPS_LINE]:
            name = kernel_of(ev)
            if lo <= ev.start < hi:
                calls[name] += 1
                call_s[name] += ev.end - ev.start
            s, e = clip(ev.start, ev.end, lo, hi)
            if e > s:
                spans.append((s, e))
                kernel_s[name] += e - s
        merged = union(spans)
        busy_total += sum(e - s for s, e in merged)
        executions += sum(1 for ev in pl.lines.get(MODULES_LINE, [])
                          if lo <= ev.start < hi)
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                gaps.append((g1 - g0, g0, g1))
    gaps.sort(key=lambda g: -g[0])
    labelled = [(s, _label(g0, g1, bench, other)) for s, g0, g1 in gaps[:10]]
    return Reduced(window=(lo, hi), devices=len(devices),
                   busy_s=busy_total / len(devices), kernel_s=dict(kernel_s),
                   kernel_calls={k: (calls[k], call_s[k]) for k in calls},
                   executions=executions, gaps=labelled)
