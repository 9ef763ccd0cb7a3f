"""What a metric reader is given: the run's requests and counters, the
work counts, the chip's peaks and (traced runs) the reduced trace.

A reader is ``metrics/<name>.py`` with ``read(ctx) -> float | None``;
``None`` means it found nothing to read, and the metric is left out."""
from __future__ import annotations

import functools
import math


class ShareError(Exception):
    """A share of a roofline or a peak read above 100%: the work is
    counted too high or the time leaves part of the work out."""


def share_percent(least_s: float, took_s: float, what: str) -> float | None:
    """``least_s / took_s`` in percent; None where nothing took time."""
    if not took_s > 0:
        return None
    pct = 100.0 * least_s / took_s
    if pct > 100.0:
        raise ShareError(f"{what} reads {pct:.2f}% (least {least_s!r} s, "
                         f"took {took_s!r} s)")
    return pct


def nearest_rank(values, q: float) -> float:
    """The ``q`` quantile by nearest rank (no interpolation)."""
    v = sorted(values)
    if not v:
        return math.nan
    return v[max(0, math.ceil(q * len(v)) - 1)]


class Context:
    def __init__(self, cell, args, env, out: dict, trace_dir=None):
        self.cell = cell
        self.args = args
        self.env = env
        self.out = out
        self.peaks = env.device["peaks"]
        self.chips = cell.chips
        self.trace_dir = trace_dir

    @property
    def window(self) -> tuple[float, float]:
        return self.out["start"], self.out["end"]

    @property
    def seconds(self) -> float:
        start, end = self.window
        return end - start

    def in_window(self):
        """Requests due inside the window."""
        start, end = self.window
        return [r for r in self.out["requests"] if start <= r.due < end]

    def answered_in_window(self):
        """Requests answered without error inside the window."""
        start, end = self.window
        return [r for r in self.out["requests"]
                if r.error is None and r.done is not None
                and start <= r.done <= end]

    @functools.cached_property
    def trace(self):
        from chipbench import devtrace

        if self.trace_dir is None:
            return None
        return devtrace.reduce(devtrace.load(self.trace_dir))

    def site_work(self, batch: int) -> list[dict]:
        return self.out["site_work"][batch]
