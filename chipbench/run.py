"""Run one benchmark cell and print its result line.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics (a few seconds of the window are traced).  The last line
of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, (traced) ``breakdown``, and last
``checks``, each number compared beside its limit; the same comparisons
are the last lines of standard error.  With no TPU, too few chips or a
chip missing from ``peaks.json`` the run exits 3 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

from chipbench import check, spec  # noqa: E402

TRACE_DIR = spec.REPO / ".chipbench_trace"


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _finite(x):
    return x if not isinstance(x, float) or math.isfinite(x) else None


def result(cell, out: dict, ctx, device: dict, trace: bool) -> dict:
    metrics = {}
    for m in cell.metrics(trace):
        value = spec.load_module(cell.root, "metrics", m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    checks = out["checks"]
    line = {
        "correct": check.passed(checks),
        "attempted": len(out["requests"]),
        "failed": checks["unanswered"]["value"],
        "metrics": metrics,
        "device": {k: device[k] for k in ("platform", "kind", "count")},
    }
    line["device"]["memory_peak_bytes"] = out["memory_peak_bytes"]
    if trace:
        line["device"]["busy_s"] = ctx.trace.busy_s
        line["device"]["window_s"] = ctx.trace.window_s
        line["breakdown"] = ctx.trace.breakdown()
    line["checks"] = {k: {"value": _finite(c["value"]), "limit": c["limit"]}
                      for k, c in checks.items()}
    return line


def main(argv=None, root=spec.HERE) -> int:
    args = parse(argv)
    src = spec.REPO / "src"
    if not (src / "repro").is_dir():
        print(f"chipbench: no program sources under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    cell = spec.load_cell(args.workload, root=root)
    from chipbench import context, env as env_mod

    trace_dir = None
    if args.trace:
        trace_dir = TRACE_DIR / args.workload
        shutil.rmtree(trace_dir, ignore_errors=True)
    try:
        env = env_mod.Env(T_START, cell.chips, trace_dir)
    except env_mod.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    system = spec.load_module(cell.root, "systems", cell.config["system"])
    out = system.run(cell, args, env)
    if out["window_compiles"] or out["recompiles"]:
        raise RuntimeError(
            f"{out['window_compiles']} compiles and {out['recompiles']} "
            f"program cache misses inside the measured window")
    ctx = context.Context(cell, args, env, out, trace_dir)
    line = result(cell, out, ctx, env.device, bool(args.trace))
    phases = ", ".join(f"{k} {v:.3f}" for k, v in out["setup_phases"].items())
    print(f"setup_s {out['setup_s']:.3f} ({phases}), reference "
          f"{out['reference_s']:.3f} s, {len(out['requests'])} requests",
          file=sys.stderr)
    print("\n".join(check.describe(out["checks"])), file=sys.stderr,
          flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SystemExit:
        raise
    except BaseException:
        traceback.print_exc()
        sys.exit(1)
