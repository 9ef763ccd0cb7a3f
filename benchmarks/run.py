"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows and writes per-module
``BENCH_<module>.json`` (machine-readable; CI uploads them as artifacts and
the bench-gate job diffs them against ``benchmarks/baseline/`` so the perf
trajectory is tracked — and gated — across PRs).

  fig3_patterns    <- paper Fig 3 + Fig 4 (pattern profile, immediates)
  fig11_cycles     <- paper Fig 11 (cycles/inference, v0..v4)
  fig12_energy     <- paper Fig 12 (energy/inference, eq. 1)
  table8_resources <- paper Table 8 / Fig 10 (resource overhead proxies)
  table10_memory   <- paper Table 10 (DM/PM per version)
  kernel/*         <- Pallas kernel micro-benches (interpret mode)
  ratio/*          <- calibrated pallas-vs-ref ratios on the conformance
                      grid (gated by benchmarks.gate per-row noise floors)
  roofline/*       <- dry-run roofline terms (assignment §Roofline)
  compile/*        <- marvel.compile AOT path (compile-once-call-many)
  serving/*        <- async serving tier (throughput, p99, occupancy)

A module that raises is reported, the remaining modules still run, and the
process exits non-zero — so the CI bench step actually fails instead of
shipping a partial trajectory.

Usage: python -m benchmarks.run [module ...]   (default: all)
"""
from __future__ import annotations

import sys
import traceback

from benchmarks import common


def main() -> None:
    from benchmarks import (
        bench_compile, bench_cycles, bench_energy, bench_kernels,
        bench_memory, bench_patterns, bench_ratio, bench_resources,
        bench_roofline, bench_serving,
    )
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    print("name,us_per_call,derived")
    mods = {
        "patterns": bench_patterns, "cycles": bench_cycles,
        "energy": bench_energy, "resources": bench_resources,
        "memory": bench_memory, "kernels": bench_kernels,
        "ratio": bench_ratio, "roofline": bench_roofline,
        "compile": bench_compile, "serving": bench_serving,
    }
    only = set(sys.argv[1:])
    unknown = only - set(mods)
    if unknown:
        raise SystemExit(f"unknown benchmark module(s) {sorted(unknown)}; "
                         f"choose from {sorted(mods)}")
    failed: list[str] = []
    for name, mod in mods.items():
        if only and name not in only:
            continue
        start = len(common.CSV_ROWS)
        try:
            mod.run()
        except Exception:
            failed.append(name)
            traceback.print_exc()
            continue  # keep emitting the other modules' artifacts
        common.write_bench_json(name, common.CSV_ROWS[start:])
    if failed:
        raise SystemExit(f"benchmark module(s) failed: {failed}")


if __name__ == "__main__":
    main()
