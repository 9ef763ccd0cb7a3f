"""Offered-load sweep of one open-loop cell, all in one process with one
set-up, to find the knee: the highest rate served without a growing
backlog.  With a closed cell it runs the closed window once, so that a
level other than the configuration's (``--level v0``) can be read beside
it.

    python3 -m chipbench.sweep --workload resnet50-224.poisson \
        --rates 200,400,600,800 --seconds 8 --seed 5

Prints one JSON line per rate: requests offered, answered inside the
window, the share answered within the window, latency p50/p95 from the
scheduled send time, batches, and images per batch.  A rate is sustained
where no backlog grows: at least ``SUSTAINED_SHARE`` of its requests are
answered inside the window (the rest are the last few sent before the
close, still in flight).  After the last window every answer is
compared with the reference, as a run compares them, and one line per
window says whether it was correct.
"""
from __future__ import annotations

import argparse
import asyncio
import gc
import json
import sys
import time

T_START = time.perf_counter()

from chipbench import check, spec  # noqa: E402
from chipbench.context import nearest_rank  # noqa: E402

SUSTAINED_SHARE = 0.98


def summarize(w: dict, rate) -> dict:
    reqs = [r for r in w["requests"] if w["start"] <= r.due < w["end"]]
    ok = [r for r in reqs if r.error is None and r.done is not None]
    lat = sorted((r.done - r.due) * 1e3 for r in ok)
    inside = sum(1 for r in ok if r.done <= w["end"])
    seconds = w["end"] - w["start"]
    b0, b1 = w["engine_before"], w["engine_after"]
    batches = b1["batches"] - b0["batches"]
    return {
        "rate_per_s": rate, "offered": len(reqs), "failed": len(reqs) - len(ok),
        "answered_in_window": inside,
        "answered_share": inside / max(len(reqs), 1),
        "images_per_s": inside / seconds,
        "p50_ms": nearest_rank(lat, 0.5), "p95_ms": nearest_rank(lat, 0.95),
        "batches": batches,
        "batch_mean": (b1["completed"] - b0["completed"]) / max(batches, 1),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--level", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(spec.REPO / "src"))
    cell = spec.load_cell(args.workload)
    from chipbench import env as env_mod
    from chipbench.systems import cnn

    env = env_mod.Env(T_START, cell.chips, None)
    built = cnn.Built(cell, args.seed, env, level=args.level)
    rates = ([float(r) for r in args.rates.split(",")]
             if cell.traffic["kind"] == "open" else [None])

    windows = []

    async def sweep():
        async with built.engine:
            await built.warm()
            print(json.dumps({"setup_s": env.since_start(),
                              "level": args.level or cell.config["level"]}),
                  flush=True)
            for i, rate in enumerate(rates):
                traffic = dict(cell.traffic)
                if rate is not None:
                    traffic["rate_per_s"] = rate
                w = await built.window(traffic, args.seed + i, args.seconds,
                                       env)
                windows.append(w)
                row = summarize(w, rate)
                row["sustained"] = row["answered_share"] >= SUSTAINED_SHARE
                print(json.dumps(row), flush=True)

    asyncio.run(sweep())
    # every window's answers against the reference, as a run checks them
    ref, cfg, params, images = built.ref, built.cfg, built.params, built.images
    del built
    gc.collect()
    want = cnn.reference_logits(ref, cfg, params, images)
    for rate, w in zip(rates, windows):
        checks = check.compare(w["requests"], want, cell.limits)
        print(json.dumps({"rate_per_s": rate, "correct": check.passed(checks),
                          "checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
