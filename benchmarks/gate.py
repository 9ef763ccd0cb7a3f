"""Perf-trajectory gate: diff BENCH_*.json against a baseline snapshot.

CI runs the benchmark suite, then this module compares the fresh
``BENCH_*.json`` files against the committed ``benchmarks/baseline/``
snapshot (or a directory of artifacts downloaded from the previous main
run).  Three metric classes are *gated* (regression beyond tolerance fails
the job):

* ``*speedup*`` and ``paper_band`` — higher is better (booleans parse to
  1.0/0.0, so a CNN dropping out of the paper's 2x band is a 1.0 -> 0.0
  regression, not a silently-vanished metric);
* ``rv32_v*`` / ``tpu_v*`` on cycles rows — lower is better (any ladder
  level, ``v0``..``v10``+);
* ``*_ratio`` on rows that carry a ``noise_floor`` metric — higher is
  better, gated at ``max(--tol, noise_floor)`` per row.  The noise floor is
  the calibrated runner's own variance estimate
  (``benchmarks/calibrate.py``), so the measured pallas-vs-ref lane
  (``benchmarks/bench_ratio.py``) gates without flaking; ratio-named
  wall-clock metrics on rows *without* a noise floor (``cache_ratio``)
  stay informational.

Raw wall-clock metrics (``us_per_call``, ``req_s``, ``p99_ms`` ...) vary
with the runner, so they are reported in the delta table but never gate.
A gated metric whose baseline is 0 can still regress: the delta is
reported as +/-inf and flagged ``leaving zero`` (growing from 0 fails
lower-is-better metrics; falling from 0 fails higher-is-better ones).

The delta table is written to ``$GITHUB_STEP_SUMMARY`` when set (the job
summary page), and always printed to stdout.

Usage: python -m benchmarks.gate [--baseline benchmarks/baseline]
                                 [--current .] [--tol 0.15] [--strict]
"""
from __future__ import annotations

import argparse
import glob
import json
import math
import os
import re
import sys

GATE_HIGHER = re.compile(r"speedup|^paper_band$")
GATE_LOWER = re.compile(r"^(rv32|tpu)_v\d+$")
GATE_RATIO = re.compile(r"_ratio$")
# per-row metadata, never a gated metric itself
NEVER_GATE = frozenset({"noise_floor"})


def load_rows(directory: str) -> dict[str, dict[str, float]]:
    """All BENCH_*.json rows in ``directory``: name -> numeric metrics.

    Malformed rows (no ``name``) are warned about and skipped — a snapshot
    edited by hand must degrade the diff, never KeyError the gate."""
    rows: dict[str, dict[str, float]] = {}
    for path in sorted(glob.glob(os.path.join(directory, "BENCH_*.json"))):
        with open(path) as f:
            for row in json.load(f):
                if not isinstance(row, dict) or "name" not in row:
                    print(f"warning: skipping malformed row in {path}: "
                          f"{row!r}", file=sys.stderr)
                    continue
                rows[row["name"]] = parse_metrics(row)
    return rows


def parse_metrics(row: dict) -> dict[str, float]:
    """The numeric metrics of one row: us_per_call + parsed derived k=v's.

    Presence (not truthiness) keeps a legitimate ``us_per_call == 0.0``;
    booleans parse to 1.0/0.0 so flag metrics (``paper_band=True``) are
    gateable instead of silently dropped by ``float("True")``."""
    out: dict[str, float] = {}
    if row.get("us_per_call") is not None:
        out["us_per_call"] = float(row["us_per_call"])
    for part in str(row.get("derived", "")).split(";"):
        if "=" not in part:
            continue
        key, _, val = part.partition("=")
        val = val.strip()
        if val in ("True", "False"):
            out[key.strip()] = 1.0 if val == "True" else 0.0
            continue
        try:
            out[key.strip()] = float(val)
        except ValueError:
            continue
    return out


def gate_direction(row_name: str, key: str,
                   metrics: dict[str, float] | None = None) -> int:
    """+1: higher is better (gated); -1: lower is better (gated); 0: not
    gated (wall-clock / informational).

    ``*_ratio`` metrics gate only when ``metrics`` carries a
    ``noise_floor`` — the calibrated-runner contract.  Rows without one
    (``cache_ratio`` ...) are raw wall-clock and stay
    informational."""
    if key in NEVER_GATE:
        return 0
    if GATE_HIGHER.search(key):
        return +1
    if "cycles" in row_name and GATE_LOWER.match(key):
        return -1
    if (GATE_RATIO.search(key) and metrics is not None
            and "noise_floor" in metrics):
        return +1
    return 0


def compare(baseline: dict, current: dict, tol: float
            ) -> tuple[list[dict], list[str], list[str]]:
    """Per-metric deltas for rows present in both, plus gated-but-missing
    baseline rows and brand-new gated current rows.

    Both structural changes are *reported*, never a hard failure (the
    baseline snapshot trails the code by one merge whenever a PR adds or
    retires a benchmark): a vanished row fails only under ``--strict``; a
    new row just has no trajectory yet — it starts gating once it lands in
    the snapshot."""
    deltas, missing, added = [], [], []
    for name, base_metrics in sorted(baseline.items()):
        cur_metrics = current.get(name)
        if cur_metrics is None:
            if any(gate_direction(name, k, base_metrics)
                   for k in base_metrics):
                missing.append(name)
            continue
        for key, base in base_metrics.items():
            if key not in cur_metrics:
                continue
            cur = cur_metrics[key]
            if base:
                delta = (cur - base) / abs(base)
            else:
                # a zero baseline has no scale — report leaving zero as an
                # infinite move so it can never hide a regression
                delta = math.copysign(math.inf, cur) if cur else 0.0
            direction = gate_direction(name, key, base_metrics)
            eff_tol = tol
            if direction and GATE_RATIO.search(key):
                # measured ratios gate at their own noise floor (per-row,
                # from the calibrated runner) when it exceeds --tol
                eff_tol = max(tol, base_metrics.get("noise_floor", 0.0),
                              cur_metrics.get("noise_floor", 0.0))
            regressed = (
                direction != 0 and (-direction * delta) > eff_tol
            )
            deltas.append({
                "row": name, "metric": key, "baseline": base,
                "current": cur, "delta": delta, "gated": direction != 0,
                "regressed": regressed, "tol": eff_tol,
                "leaving_zero": base == 0 and cur != 0,
            })
    for name, cur_metrics in sorted(current.items()):
        if name not in baseline and any(
            gate_direction(name, k, cur_metrics) for k in cur_metrics
        ):
            added.append(name)
    return deltas, missing, added


def markdown_table(deltas: list[dict], tol: float) -> str:
    """Gated metrics always; ungated ones only when they moved > tol (keeps
    the summary readable — kernels alone emit dozens of wall-clock rows)."""
    lines = [
        "| row | metric | baseline | current | delta | gate |",
        "|---|---|---:|---:|---:|---|",
    ]
    for d in deltas:
        if not d["gated"] and abs(d["delta"]) <= tol:
            continue
        status = ("**FAIL**" if d["regressed"]
                  else "ok" if d["gated"] else "info")
        if d.get("leaving_zero"):
            status += " (leaving zero)"
        delta = ("+inf" if d["delta"] == math.inf
                 else "-inf" if d["delta"] == -math.inf
                 else f"{d['delta']:+.1%}")
        lines.append(
            f"| {d['row']} | {d['metric']} | {d['baseline']:.4g} "
            f"| {d['current']:.4g} | {delta} | {status} |"
        )
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--baseline", default="benchmarks/baseline")
    ap.add_argument("--current", default=".")
    ap.add_argument("--tol", type=float, default=0.15,
                    help="max allowed regression on gated metrics")
    ap.add_argument("--strict", action="store_true",
                    help="also fail when a gated baseline row disappears")
    args = ap.parse_args(argv)

    baseline = load_rows(args.baseline)
    if not baseline:
        print(f"no BENCH_*.json under {args.baseline}; nothing to gate")
        return 0
    current = load_rows(args.current)
    deltas, missing, added = compare(baseline, current, args.tol)
    failures = [d for d in deltas if d["regressed"]]

    table = markdown_table(deltas, args.tol)
    n_gated = sum(d["gated"] for d in deltas)
    verdict = (
        f"bench-gate: {n_gated} gated metrics, {len(failures)} regression(s) "
        f"beyond {args.tol:.0%}, {len(missing)} gated row(s) missing, "
        f"{len(added)} new gated row(s)"
    )
    summary = f"## Perf trajectory vs baseline\n\n{table}\n\n{verdict}\n"
    if missing:
        summary += "\nmissing gated rows: " + ", ".join(missing) + "\n"
    if added:
        summary += ("\nnew gated rows (no trajectory yet — refresh the "
                    "baseline snapshot): " + ", ".join(added) + "\n")
    print(summary)
    step_summary = os.environ.get("GITHUB_STEP_SUMMARY")
    if step_summary:
        with open(step_summary, "a") as f:
            f.write(summary)

    for d in failures:
        print(f"REGRESSION {d['row']} {d['metric']}: "
              f"{d['baseline']:.4g} -> {d['current']:.4g} "
              f"({d['delta']:+.1%})", file=sys.stderr)
    if failures or (args.strict and missing):
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
