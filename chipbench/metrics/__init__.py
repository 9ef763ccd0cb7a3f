"""Metric readers, one file per metric, found by the metric's name in
``BENCHMARK.json``: ``read(ctx) -> float | None`` (see
:mod:`chipbench.context`)."""
